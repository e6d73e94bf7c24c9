"""latent_kernel_roofline (%): the fused attention kernel in the latent
layers against the chip's bf16 peak. Least time = the kernel's products AS
EXECUTED (lm_latent_flops.kernel_flops: the pairs of blocks on and below
the diagonal, each multiplied whole; forward the 192-wide scores and the
128-wide values' product, backward the scores again, dv, dp, dk and dq) x
the kernel's calls a step that the trace counts, over `bf16_flops` of
peaks.json; divided by the device time of `causal_attention_fwd|bwd` under
the layers' `attn`. Bound by compute. A reading over 100 means the count is
wrong."""

from yardstick import latent_scope_reduce


def read(run):
    out = latent_scope_reduce.per_step(run)
    flops = (run.facts.get("latent") or {}).get("kernel_flops")
    if out is None or run.peaks is None or not flops:
        return None
    ms = out["ms"]["kernel_fwd"] + out["ms"]["kernel_bwd"]
    if ms <= 0.0:
        return None
    done = sum(flops[d] * out["calls"][d] for d in ("fwd", "bwd"))
    return 100.0 * done / run.peaks["bf16_flops"] * 1e3 / ms
