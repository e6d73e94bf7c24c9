"""shared_kv_attn_device_ms (ms): device time per train step of everything
under `layer_<i>/attn` of the full attention layer and of the cross layers:
the writer and the readers of ONE layer's keys and values (the full layer's
projections of them and, in the backward pass, the sum of their gradient
over its readers among them), on the busiest chip over the profiled interval
(yardstick/sambay_scope_reduce.py)."""

from yardstick import sambay_scope_reduce


def read(run):
    ms = sambay_scope_reduce.per_step_ms(run)
    return None if ms is None else ms["attn_full"] + ms["attn_cross"]
