"""gated_attn_device_ms (ms): device time per train step of everything
under `layer_<i>/attn` of the gated attention layers (heads 256 wide, a
quarter of each rotated, the query projection twice as wide for the gate),
forward and backward: the norm before it, the projections, the norms of q
and k, the rotation, the fused kernel's calls, the gate and the output
projection, on the busiest chip over the profiled interval
(yardstick/gdn_scope_reduce.py)."""

from yardstick import gdn_scope_reduce


def read(run):
    ms = gdn_scope_reduce.per_step_ms(run)
    return None if ms is None else ms["attn"]
