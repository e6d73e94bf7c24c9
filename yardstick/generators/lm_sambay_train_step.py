"""Generator of language-model training traffic for a decoder-hybrid-decoder
model (Mamba-1 layers, window and full differential attention, gated memory
units, cross-attention over one layer's keys and values), no experts:
`lm_ssm_train_step.py`'s loop and checks as they are (a trainer's loop around
the program's jitted train step, the model described as data by the
configuration file's `model` block; before the first steps the plain
reference's loss, logits and, leaf by leaf, the update of the timed
executable's first step; every loss read in the window finite), run against
reference/lm_sambay_train_step.py, which the harness finds by this kind's
name. What differs is what the readers are told: the model's FLOPs with
128-wide values under 64-wide scores and the selective scan as the
recurrence, and that scan's least bytes (`lm_sambay_flops.py`). The `model`
block carries `ssm_heads` and `ssm_head_dim` of 0 (a Mamba-1 layer has no
heads; the program's defaults), which the imported loop's own count of a
Mamba-2 scan reads before this file replaces it."""

from __future__ import annotations

import jax.numpy as jnp

from yardstick import lm_sambay_flops
from yardstick.generators import lm_ssm_train_step as ssm_step

build = ssm_step.build      # the scope reducers compile the step from it


def run(run) -> None:
    ssm_step.run(run)
    model = run.config["model"]
    batch, seq = int(run.traffic["batch"]), int(run.traffic["seq"])
    run.facts["flops_per_step"] = lm_sambay_flops.flops_per_step(
        model, batch, seq)
    run.facts["scan"] = {
        "layers": lm_sambay_flops.layer_mixers(model).count("mamba"),
        "least_bytes": lm_sambay_flops.scan_least_bytes(
            model, batch, seq, jnp.dtype(model["dtype"]).itemsize)}
