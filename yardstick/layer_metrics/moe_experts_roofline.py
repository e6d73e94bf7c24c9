"""moe_experts_roofline (%): the experts' grouped matrix multiplications
against the chip's bf16 peak. Least time = the FLOPs a step's token-slots
need through their experts' matrices, forward and backward
(lm_flops.expert_flops_per_layer x layers: 3 x 2 x rows x matrices x d x f,
rows = tokens x experts per token) over `bf16_flops` of peaks.json; divided
by the device time per step under `mlp/experts` (the grouped
multiplications, whoever wrote their kernel, and the activation between
them). Bound by compute: at 1024 rows an expert each weight byte is used
1024 times."""

from yardstick import moe_scope_reduce


def read(run):
    ms = moe_scope_reduce.per_step_ms(run)
    flops = run.facts.get("expert_flops_per_step")
    if ms is None or run.peaks is None or not flops or ms["experts"] <= 0.0:
        return None
    least_ms = flops / run.peaks["bf16_flops"] * 1e3
    return 100.0 * least_ms / ms["experts"]
