"""step_build_s (s): the seconds it took to build the train step itself:
trace + lowering + backend compile (or the cache's read in its place) of the
functions the program's step builders named (`build.step`, `local_step` for
`transformer_train_step`), from `build.by_fun` of the pvar snapshot at the
window's begin (`yardstick/build_reduce.py`). What the generator's
`step.lower(...).compile()` costs, timed by JAX inside it and by no wrapper
around it: a frame around a lowering would move a Mosaic kernel's cache key.
The rest of the three `build_*_s` is the benchmark's own programs (weights,
tokens, the forward pass, the reference). A train cell arms nothing, so no
other reader of its cells turns span sampling on: this one does (`prepare`),
and prints the `setup:` spans that set-up then publishes
(`build.trace|lower|compile`, `kernels.import`; those of the traced run's
own compiles after the window are left out) beside its number."""

from yardstick import build_reduce, span_reduce

prepare = span_reduce.prepare


def read(run):
    fam = build_reduce.family(run)
    if fam is None or not fam["step"]:
        return None
    rows = [(name, fam["by_fun"][name]) for name in fam["step"]
            if name in fam["by_fun"]]
    if not rows:
        return None
    run.row("step build (function: events and seconds a phase): " + "  ".join(
        f"{name}: " + " ".join(f"{p} x{row[p]['n']} {row[p]['s']:.3f}"
                               for p in build_reduce.PHASES)
        for name, row in rows))
    build_reduce.setup_spans_row(run, fam)
    return float(sum(row[p]["s"] for _name, row in rows
                     for p in build_reduce.PHASES))
