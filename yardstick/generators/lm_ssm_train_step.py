"""Generator of language-model training traffic for a model with
state-space (Mamba-2) layers among its attention layers, no experts:
`lm_kinds_train_step.py`'s trainer's loop (the program's jitted train step,
the model described as data by the configuration file's `model` block; the
same block timing, checks and result line) with a loop of its own, because
that one counts a routing this model does not have. The file's published
keys go to the plain reference (reference/lm_ssm_train_step.py). The traffic
file gives the token batches as `lm_train_step.py` reads them (`batch`,
`seq`, `pool`, `block_steps`); token ids are uniform over the vocabulary,
one document a sequence.

One sample per block: (first dispatch -> the block's loss on the host) /
block_steps; `train_tokens_per_s` = batch x seq / the median. Correctness,
all of it outside the window: before each of the first `compare_steps` steps
the reference computes that step's loss in float32 from the system's own
parameters at that moment, one layer's weights at a time, the state-space
layers as the recurrence over time (`loss_tolerance`); before the first
step the program's logits against the reference's, rms of the difference
over rms of the reference's (`logits_tolerance`); the update of the first
step, made by the timed executable itself, against the reference's gradient
leaf by leaf, each leaf held to its own limit (`update_off_by` below:
`lm_train_step.py`'s reading, with the leaves of `update_pooled` summed over
the layers and a rule for a leaf that all but stands still; `update_limits`,
imported; `update_tolerance`, by leaf); every loss read in the window
finite."""

from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from yardstick import lm_ssm_flops, stats
from yardstick.generators.lm_kinds_train_step import update_limits
# `build`: (model, mesh, step, specs) of the cell; the scope reducers compile
# the step from it too
from yardstick.generators.lm_train_step import build
from yardstick.harness import annotate


FEW = 32    # movers under which a leaf's update is too little to read a share from


def update_off_by(ref, cfg: dict, before, after, tokens, labels) -> dict:
    """`lm_train_step.update_off_by`'s reading, by leaf (the reference's
    names): with g the reference's float32 gradient at `before` (one layer at
    a time) and want = before - lr x g rounded to the leaf's dtype, as the
    step rounds it, sum (after - want)^2 / sum (want - before)^2, the share
    of the expected update's energy by which the system's parameters miss
    it. Most elements do not move (lr x g is below half a unit in the last
    place) and the ones that do, move by a whole unit, so the reading counts
    the movers on which the two sides disagree, weighed by their units, and
    is as steady as its movers are many. A leaf is read in its worst layer,
    but the leaves the file names in `update_pooled` over all their layers
    together, both sums taken before the division: a head's three scalars of
    the recurrence are 64 float32 numbers a layer, about ten of which move.
    Where the reference moves fewer than `FEW` elements (in the layer, or in
    all of them for a pooled leaf: the convolution's taps, and the query and
    key projections under scores this flat, get gradients that move a
    handful of elements or none) there is no share to read, one mover in
    dispute among three would read 0.33 and among none inf: there the system
    must move fewer than 2 x `FEW` elements, and reads 0, else inf."""
    lr, pooled = float(cfg["lr"]), set(cfg.get("update_pooled", ()))

    @jax.jit
    def sums(b, a, g):
        # rounded by `reduce_precision`: the compiler may drop a conversion
        # to the parameters' dtype and back (the v5e's does, PR 25)
        kind = jnp.finfo(b.dtype)
        b32, a32 = b.astype(jnp.float32), a.astype(jnp.float32)
        want = lax.reduce_precision(b32 - lr * g, kind.nexp, kind.nmant)
        return (jnp.sum(jnp.square(a32 - want)),
                jnp.sum(jnp.square(want - b32)),
                jnp.sum(want != b32), jnp.sum(a32 != b32))

    before, after = ref.from_system(before), ref.from_system(after)
    by_leaf = {}        # name -> [(missed, moved, movers, the system's)]
    for i, grads in ref.make_grads_from(cfg)(before, tokens, labels):
        b, a = (before, after) if i is None else \
            (before["layers"][i], after["layers"][i])
        for name, g in grads.items():
            by_leaf.setdefault(name, []).append(
                tuple(float(v) for v in sums(b[name], a[name], g)))

    def share(missed, moved, movers, moved_here):
        if movers < FEW:
            return 0.0 if moved_here < 2 * FEW else math.inf
        return missed / moved
    return {name: share(*map(sum, zip(*rows))) if name in pooled
            else max(share(*row) for row in rows)
            for name, rows in by_leaf.items()}


def run(run) -> None:
    from tpu_mpi.models.transformer import transformer_forward, transformer_init

    cfg, tr = run.config, run.traffic
    batch, seq = int(tr["batch"]), int(tr["seq"])
    pool, block_steps = int(tr["pool"]), int(tr["block_steps"])
    model, mesh, step, specs = build(run)
    shard = jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
    data = NamedSharding(mesh, P("dp", "sp"))

    # weights and tokens from the seed, on the device, one jitted call each
    key = jax.random.key(run.seed)
    params = jax.jit(lambda k: transformer_init(k, model),
                     out_shardings=shard)(jax.random.fold_in(key, 0))

    def make_tokens(k):
        tok = jax.random.randint(k, (pool, batch, seq), 0, model.vocab)
        return [(tok[i], jnp.roll(tok[i], -1, axis=1)) for i in range(pool)]
    batches = jax.jit(make_tokens, out_shardings=data)(
        jax.random.fold_in(key, 1))
    jax.block_until_ready((params, batches))
    run.phase("weights and tokens")

    compiled = step.lower(params, *batches[0]).compile()
    forward = jax.jit(lambda p, tok: transformer_forward(model, p, tok))
    off_by = jax.jit(lambda got, want: jnp.sqrt(
        jnp.sum(jnp.square(got - want)) / jnp.sum(jnp.square(want))))
    run.phase("step executable")
    state = {"params": params, "i": 0}
    del params                      # the step overwrites what it is given

    def block(nsteps: int):
        t0 = time.perf_counter()
        for _ in range(nsteps):
            tok, lab = batches[state["i"] % pool]
            with annotate("ys:step"):
                state["params"], loss = compiled(state["params"], tok, lab)
            state["i"] += 1
        with annotate("ys:readback"):
            value = float(loss)
        return time.perf_counter() - t0, value

    # -- the reference's loss from the system's own parameters, then the step
    ref = run.cell.reference()
    loss_from = ref.make_loss_from(cfg)
    nref, want, got = int(cfg["compare_steps"]), [], []
    for n in range(nref):
        tok, lab = batches[state["i"] % pool]
        loss, logits = loss_from(ref.from_system(state["params"]), tok, lab,
                                 logits=n == 0)
        if n == 0:
            logits_off = float(off_by(forward(state["params"], tok), logits))
            del logits
            before = jax.device_get(state["params"])    # the step overwrites
        want.append(loss)
        got.append(block(1)[1])
        if n == 0:
            update_off = update_off_by(ref, cfg, before, state["params"],
                                       tok, lab)
            del before
            run.phase("reference loss, first step, reference gradient")
    run.phase("reference losses and first steps")
    tol, ltol = float(cfg["loss_tolerance"]), float(cfg["logits_tolerance"])
    limits = update_limits(update_off, cfg["update_tolerance"])
    worst = max(abs(g - w) for g, w in zip(got, want))
    print(f"first losses: system {got}  reference {want}  "
          f"worst |diff| {worst:.3e} (tolerance {tol})  logits off by "
          f"{logits_off:.3e} of their rms (tolerance {ltol})")
    print("first update off by, the share of the expected update's energy "
          "in the worst layer, or over all layers for "
          f"{sorted(cfg.get('update_pooled', ()))} (the leaf's tolerance): "
          + "  ".join(
              f"{k} {v:.3e} ({limits[k]})" for k, v in update_off.items()))
    correct = all(math.isfinite(g) for g in got) and worst <= tol \
        and logits_off <= ltol \
        and all(v <= limits[k] for k, v in update_off.items())
    block(block_steps)                      # one block as measured
    run.memory_row("after the warm-up")

    # -- the window -----------------------------------------------------------
    run.window_begin()
    times, steps, failed, last = [], 0, 0, got[-1]
    while not times or run.elapsed() < run.seconds:
        run.trace_tick(steps)
        dt, last = block(block_steps)
        times.append(dt)
        steps += block_steps
        if not math.isfinite(last):
            failed += block_steps
    run.window_end(steps)

    q = stats.quartiles([t / block_steps for t in times])
    tokens_per_s = batch * seq / q["median"]
    run.row(f"train step [{batch} x {seq}] n={q['n']} blocks of {block_steps}  "
            f"per-step q1 {q['q1'] * 1e3:.3f} ms  median {q['median'] * 1e3:.3f} "
            f"ms  q3 {q['q3'] * 1e3:.3f} ms  spread {100 * q['spread']:.2f}%  "
            f"last loss {last:.4f}")
    mixers = lm_ssm_flops.layer_mixers(cfg["model"])
    run.results = {"metrics": {"train_tokens_per_s": tokens_per_s},
                   "correct": bool(correct and not failed),
                   "attempted": steps, "failed": failed}
    run.facts = {"ops": steps, "per_op_s": q["median"],
                 "flops_per_step": lm_ssm_flops.flops_per_step(
                     cfg["model"], batch, seq),
                 "scan": {"layers": mixers.count("ssm"),
                          "least_bytes": lm_ssm_flops.scan_least_bytes(
                              cfg["model"], batch, seq,
                              jnp.dtype(cfg["model"]["dtype"]).itemsize)}}
