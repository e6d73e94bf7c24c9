"""held_experts_roofline (%): the held experts' grouped multiplications
against the chip's bf16 peak. Least time = the FLOPs the rows that landed
on the held experts need through their three matrices, forward and backward
(lm_kinds_flops.held_expert_flops: 3 x 2 x held rows x 3 x 6144 x 2048 a
sparse layer, the rows from the program's own counter) over `bf16_flops` of
peaks.json; divided by the device time per step under `mlp/experts`. At
about 512 rows an expert each weight byte is used 512 times: bound by
compute, but the buffer's padding rows and the groups' boundaries are all
in the time."""

from yardstick import kinds_scope_reduce


def read(run):
    ms = kinds_scope_reduce.per_step_ms(run)
    flops = run.facts.get("held_expert_flops_per_step")
    if ms is None or run.peaks is None or not flops or ms["experts"] <= 0.0:
        return None
    return 100.0 * flops / run.peaks["bf16_flops"] * 1e3 / ms["experts"]
