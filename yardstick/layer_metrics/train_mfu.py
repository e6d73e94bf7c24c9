"""train_mfu (%): model-FLOP utilisation, end to end: the matrix-multiply
FLOPs one step needs (stats.transformer_flops_per_step, forward and
backward, no recomputation counted) times steps per second (the window's
median step time, host clock), over `bf16_flops` of peaks.json times the
chips used. Not a roofline share of any kernel, and it says nothing about
idle time."""


def read(run):
    if run.peaks is None or not run.facts.get("per_op_s"):
        return None
    achieved = run.facts["flops_per_step"] / run.facts["per_op_s"]
    return 100.0 * achieved / (run.peaks["bf16_flops"] * len(run.devices))
