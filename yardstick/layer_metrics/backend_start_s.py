"""backend_start_s (s): the seconds JAX's TPU client took to come up
(`jax.default_backend()` in run.py: libtpu's own start-up). `setup_s` leaves
them out, because between runs of the same code on the same machine they
differ by more than everything the repo does in set-up (PERF.md section 2);
this metric, and `device.backend_start_s` in every result line, keep them on
the record."""


def read(run):
    return None if run.rehearse else run.backend_s
