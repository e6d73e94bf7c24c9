"""norm_out_device_ms (ms): device time per train step under `norm_out`,
the sandwich's RMSNorm of each half's output before it joins the residual
(two a layer, under `attn` and under `mlp`), forward and backward, summed
over the layers (yardstick/latent_scope_reduce.py). Passes over [tokens,
d_model] bound by HBM; where the compiler fuses one into its neighbours the
time goes with the fusion's first instruction and this reads low."""

from yardstick import latent_scope_reduce


def read(run):
    ms = latent_scope_reduce.per_step_ms(run)
    return None if ms is None else ms["norm_out"] + ms["mlp_norm_out"]
