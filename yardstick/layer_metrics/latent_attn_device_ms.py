"""latent_attn_device_ms (ms): device time per train step of the ops under
`layer_<i>/attn` in a model with latent attention: the norm before the half,
both down-projections, the latents' norms, both up-projections and the cut
into heads, RoPE on the rotated parts, the fused kernel forward and backward,
the heads' part of the output projection, the sandwich's output norm and the
residual add; summed over the layers, on the busiest chip over the profiled
interval (yardstick/latent_scope_reduce.py)."""

from yardstick import latent_scope_reduce


def read(run):
    ms = latent_scope_reduce.per_step_ms(run)
    return None if ms is None else sum(
        ms[s] for s in latent_scope_reduce.ATTN)
