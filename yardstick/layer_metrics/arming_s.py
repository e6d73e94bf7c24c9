"""arming_s (s): wall seconds of set-up under the program's own arming
spans: `plan.register` (`_register_allreduce`: pinning, the registered
device fold's `fold.compile` children) and `jitted_fold.compile` (the first
call of a generic fold's signature), as the union of their top-level
brackets over all rank threads (four ranks register at once). Read from the
pvar snapshot at the window's begin (`arming_s`), which sums exactly those
spans; a program without them reports nothing."""

from yardstick import span_reduce

prepare = span_reduce.prepare


def read(run):
    begin = run.counters.get("begin", {})
    if "arming_s" not in begin or run.rehearse:
        return None
    summary = span_reduce.summarize(run)
    spans = []
    if summary is not None:     # the spans themselves, for a person: all
        from tpu_mpi import tracectx    # arming lies before the interval
        spans = [s for s in tracectx.drain(t1=summary.lo_s)
                 if s.get("trace", "").startswith("setup:")]
    by_name = {}
    for s in spans:
        rec = by_name.setdefault(s["name"], [0, 0.0])
        rec[0] += 1
        rec[1] += s["t1"] - s["t0"]
    if by_name:
        run.row("arming spans, count and seconds summed over rank threads: "
                + "  ".join(f"{n} x{c} {t:.3f}"
                            for n, (c, t) in sorted(by_name.items())))
    return float(begin["arming_s"])
