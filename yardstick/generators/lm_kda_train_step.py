"""Generator of language-model training traffic for a model whose layers are
linear attention with a delta rule decayed a key channel (KDA) or
positionless latent attention, the first before a dense gated FFN and the
others before routed experts of which this chip holds a share:
`lm_gdn_train_step.py`'s loop and checks as they are (a trainer's loop
around the program's jitted train step, the model described as data by the
configuration file's `model` block; before the first steps the plain
reference's loss, logits and, leaf by leaf, the update of the timed
executable's first step; before and after the window the program's own
count of the routing), run against reference/lm_kda_train_step.py, which the
harness finds by this kind's name. What differs is what the readers are
told: the model's FLOPs with the low-rank maps, the vector decay's scan and
the latent layers' two-width scores (`lm_kda_flops.py`), the scan's
chunked FLOPs and least bytes with a decay a channel, the held experts'
FLOPs over the layers that have experts, and the fused kernel's blocks and
products as executed in the latent layers. (The loop's own count of the
model's FLOPs reads the block as a grouped-query stack and is replaced
here; the block states `n_kv_heads` 0, the default, for it.)

One check is added to the loop's, because a whole step's loss, logits and
leaves swing by more over seeds than the scan's own precision moves them
(PR 48's probe, a chunk's decay sums and the state after each chunk rounded
to bfloat16, in the form it first ran): **the program's scan alone against
the recurrence** (`scan_off_by`). The first KDA layer's q, k, v, decay and
beta are computed by the reference in float32 from the timed program's own
first parameters and first batch, q, k and v rounded to the model's type as
the program's are; `tpu_mpi.parallel.delta.delta_scan` (whatever implements
it: the call the layer makes, chunk and all) is held against the
reference's recurrence one token at a time on those same operands, rms of
the difference over rms of the reference's over all heads, against
`scan_tolerance`. Nothing but the scan's own arithmetic separates the two,
so the reading is steady to a hundredth over seeds, and a decay sum or a
state kept in bfloat16 reads 2.2 times the sound step's and more (the
configuration's `scan_tolerance_why` has the readings)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from yardstick import lm_kda_flops, lm_kinds_flops, lm_latent_flops
from yardstick.generators import lm_gdn_train_step as gdn

build = gdn.build           # the scope reducers compile the step from it


def latent_facts(model_block: dict, batch: int, seq: int) -> dict:
    """The fused kernel's blocks for the latent layers (the program's own
    choice; None where its contract leaves the shape out) and its products
    as executed in one layer."""
    from tpu_mpi.xla import pallas_kernels as pk
    dh, dr, dv = lm_latent_flops.widths(model_block)
    blocks = pk.causal_attention_blocks(
        seq, dh, dr, dv, jnp.dtype(model_block["dtype"]))
    return {"layers": lm_kda_flops.layer_mixers(model_block).count("latent"),
            "blocks": blocks,
            "kernel_flops": None if blocks is None else
            lm_latent_flops.kernel_flops(model_block, batch, seq, blocks)}


def scan_off_by(run) -> dict:
    """How far the program's scan of the first KDA layer's operands lies
    from the reference's recurrence over the same operands, rms of the
    difference over rms of the recurrence's output: `all` over every head,
    `by_head` a head, and `rounding`, what the recurrence's own output
    reads against itself once rounded to the model's type (the share of
    `all` that any scan with such an output has). Weights and tokens from
    the seed as the loop draws them, the operands the reference's, in
    float32, q, k and v then rounded to the model's type."""
    from tpu_mpi.models.transformer import transformer_init
    from tpu_mpi.parallel import delta
    cfg, tr = run.config, run.traffic
    model, ref = build(run)[0], run.cell.reference()
    at = lm_kda_flops.layer_mixers(cfg["model"]).index("kda")
    shape = (int(tr["pool"]), int(tr["batch"]), int(tr["seq"]))
    f32, kind = jnp.float32, jnp.finfo(model.dtype)

    @jax.jit
    def operands(key):
        params = transformer_init(jax.random.fold_in(key, 0), model)
        tok = jax.random.randint(jax.random.fold_in(key, 1), shape, 0,
                                 model.vocab)[0]
        named = ref.from_system(params, model=cfg)
        lp = jax.tree.map(lambda a: a.astype(f32), named["layers"][at])
        with jax.default_matmul_precision("highest"):
            y = ref.rms_norm(named["embed_tokens"].astype(f32)[tok],
                             lp["input_layernorm"], cfg["rms_norm_eps"])
            (q, k, v, g, beta), _ = ref.kda_operands(
                cfg, lp, y, ref.kda_start(cfg, shape[1], f32)[1])
        return tuple(a.astype(model.dtype) for a in (q, k, v)) + (g, beta)

    @jax.jit
    def sums(q, k, v, g, beta):
        got = delta.delta_scan(q, k, v, g, beta, model.gdn_chunk)
        with jax.default_matmul_precision("highest"):
            want = ref.kda_recurrence(
                ref.kda_start(cfg, shape[1], f32)[0],
                *(a.astype(f32) for a in (q, k, v)), g, beta)[1]
        over = (0, 1, 3)                        # all but the heads
        # `reduce_precision`: the compiler may drop a conversion and back
        rounded = jax.lax.reduce_precision(want, kind.nexp, kind.nmant)
        return (jnp.sum(jnp.square(got.astype(f32) - want), over),
                jnp.sum(jnp.square(want), over),
                jnp.sum(jnp.square(rounded - want)))
    off, size, rounding = (np.asarray(a, np.float64) for a in sums(
        *operands(jax.random.key(run.seed))))
    return {"all": float(np.sqrt(off.sum() / size.sum())),
            "by_head": np.sqrt(off / size).tolist(),
            "rounding": float(np.sqrt(rounding / size.sum()))}


def run(run) -> None:
    scan = scan_off_by(run)
    run.phase("the scan against the recurrence")
    limit = float(run.config["scan_tolerance"])
    print(f"scan off by, the first KDA layer's operands through the "
          f"program's delta_scan against the recurrence a token at a time, "
          f"rms of the difference over rms: {scan['all']:.4e} (tolerance "
          f"{limit}; the output's own rounding {scan['rounding']:.4e})  by "
          "head " + " ".join(f"{x:.2e}" for x in scan["by_head"]))
    gdn.run(run)
    run.results["correct"] = bool(run.results["correct"]
                                  and scan["all"] <= limit)
    model = run.config["model"]
    batch, seq = int(run.traffic["batch"]), int(run.traffic["seq"])
    computed = [n for when in run.facts["held"].values()
                for n in when["computed"]]
    rows = sum(computed) / len(computed)        # a sparse layer's, the mean
    run.facts["flops_per_step"] = lm_kda_flops.flops_per_step(
        model, batch, seq, held_rows=rows)
    run.facts["held_expert_flops_per_step"] = \
        lm_kda_flops.sparse_layers(model) \
        * lm_kinds_flops.held_expert_flops(model, rows)
    del run.facts["scan"]           # a scalar decay's count: none here
    run.facts["kda_scan"] = {
        "layers": lm_kda_flops.layer_mixers(model).count("kda"),
        "chunked_flops": lm_kda_flops.scan_chunked_flops(model, batch, seq),
        "least_bytes": lm_kda_flops.scan_least_bytes(
            model, batch, seq, jnp.dtype(model["dtype"]).itemsize)}
    run.facts["latent"] = latent_facts(model, batch, seq)   # the accepted
    #                               latent_* readers' name for them
