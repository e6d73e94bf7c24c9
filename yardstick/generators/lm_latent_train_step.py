"""Generator of language-model training traffic for a model with latent
attention of which this chip holds a share of the heads, of the experts and
of the vocabulary: `lm_kinds_train_step.py`'s loop and checks as they are
(a trainer's loop around the program's jitted train step, the model
described as data by the configuration file's `model` block; before the
first steps the plain reference's loss, logits and, leaf by leaf, the update
of the timed executable's first step; before and after the window the
program's own count of the routing), run against
reference/lm_latent_train_step.py, which the harness finds by this kind's
name. What differs is what the readers are told: the model's FLOPs with the
latent's projections and two-width scores (`lm_latent_flops.py`), and the
fused kernel's blocks and products as executed under `latent`."""

from __future__ import annotations

from yardstick import lm_latent_flops
from yardstick.generators import lm_kinds_train_step as kinds

build = kinds.build         # the scope reducers compile the step from it


def latent_facts(model_block: dict, batch: int, seq: int) -> dict:
    """The fused kernel's blocks for the latent layers (the program's own
    choice; None where its contract leaves the shape out) and its products
    as executed in one layer."""
    from tpu_mpi.xla import pallas_kernels as pk
    dh, dr, dv = lm_latent_flops.widths(model_block)
    blocks = pk.causal_attention_blocks(seq, dh, dr, dv)
    return {"layers": int(model_block["n_layers"]), "blocks": blocks,
            "kernel_flops": None if blocks is None else
            lm_latent_flops.kernel_flops(model_block, batch, seq, blocks)}


def run(run) -> None:
    kinds.run(run)
    model = run.config["model"]
    batch, seq = int(run.traffic["batch"]), int(run.traffic["seq"])
    computed = [n for when in run.facts["held"].values()
                for n in when["computed"]]
    rows = sum(computed) / len(computed)        # a sparse layer's, the mean
    run.facts["flops_per_step"] = lm_latent_flops.flops_per_step(
        model, batch, seq, held_rows=rows)
    del run.facts["attention"]      # grouped-query layers' facts: none here
    run.facts["latent"] = latent_facts(model, batch, seq)
