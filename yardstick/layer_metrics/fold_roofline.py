"""fold_roofline (%): the rank-ordered fold against the chip's HBM roofline.
Least traffic of one fold is (nranks + 1) x payload bytes (read every
operand once, write the result once; stats.fold_bytes); over
`hbm_bytes_per_s` from peaks.json that is the least time; divided by the
fold executable's device time per run, from the trace's `XLA Modules` line
on the chip that folds. The fold is bound by memory bandwidth, not compute:
one add per 4-byte element.

The fold executable is known by the functions the program jits for it
(whole names: an event is `jit_<function>(<id>)`): `plain_fold`, the
rank-ordered left chain of `collective._left_chain` that the armed lane and
the legacy lane's `_jitted_fold` both compile for a reduce, and `chain`, the
armed lane's donated form (collective._registered_device_fold). A traced run
that finds neither fails: a fold that was renamed or replaced needs a
benchmark PR to name it here, and must not drop out of the result line
unseen. Defined for traffic of one size, where every fold moves the same
bytes."""

from yardstick import stats

FOLD_FUNCTIONS = ("plain_fold", "chain")


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    if not run.facts.get("payload_bytes"):
        raise RuntimeError("fold_roofline is defined for traffic of one size")
    runs, seconds = run.trace.module_seconds(*FOLD_FUNCTIONS)
    if not runs:
        raise RuntimeError(
            f"fold_roofline: the busiest chip ran none of "
            f"{['jit_' + f for f in FOLD_FUNCTIONS]} in the profiled "
            f"interval; it ran {sorted(run.trace.busiest.modules)}")
    least = stats.fold_bytes(run.facts["ranks"], run.facts["payload_bytes"]) \
        / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / runs)
