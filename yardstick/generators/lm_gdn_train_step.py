"""Generator of language-model training traffic for a model whose layers are
linear attention with a gated delta rule or gated softmax attention, each
before routed experts of which this chip holds a share:
`lm_ssm_train_step.py`'s trainer's loop (the program's jitted train step, the
model described as data by the configuration file's `model` block; the same
block timing, checks and result line) with `lm_kinds_train_step.py`'s count
of the routing, in a loop of its own because neither of those has both. What
is imported is theirs as it stands: `build`, `update_limits`, and
`update_off_by` with its `update_pooled`. The file's published keys go to
the plain reference (reference/lm_gdn_train_step.py). The traffic file gives
the token batches as `lm_train_step.py` reads them (`batch`, `seq`, `pool`,
`block_steps`); token ids are uniform over the vocabulary rows that are
here, one document a sequence.

**Every seed draws the same work** where the traffic file says so
(`router_shares`; `tied_routers` below). At random weights a stack of
delta-rule layers hands a router nearly the same vector for every token, so
it sends all tokens to the same few of its experts, and how many of those
this chip holds is the draw of the weights and of every step that trains
them: the held experts get a sixth to 2.7 times the rows a balanced router
sends them, the load moves by half the balanced rows within thirty steps, the
step's time follows the rows, and what arrives over the program's buffer
(2 x the balanced rows) runs further buffers, a tenth of a step: the
Kimi cell's rate differs by 3.5% over seeds where two runs of one seed agree
to 0.005%. A deployment's routers are balanced over its chips; these are
made so: with `router_shares` n, the held experts' columns of every router,
as `transformer_init` draws them from the seed, stand at the same place of
each of the n shares (expert j, j + held, ..., j + (n - 1) held have one
column), so a token's best expert comes with its n - 1 twins on the other
chips and every chip gets one row a token, whatever the seed. Without the
key the routers are as drawn.

One sample per block: (first dispatch -> the block's loss on the host) /
block_steps; `train_tokens_per_s` = batch x seq / the median. Correctness,
all of it outside the window: before each of the first `compare_steps` steps
the reference computes that step's loss in float32 from the system's own
parameters at that moment, one layer's weights at a time, the delta-rule
layers as the recurrence over time (`loss_tolerance`); before the first
step the program's logits against the reference's, rms of the difference
over rms of the reference's (`logits_tolerance`); the update of the first
step, made by the timed executable itself, against the reference's gradient
leaf by leaf, each leaf held to its own limit (`update_tolerance`, by leaf;
the leaves of `update_pooled` over all their layers together); every loss
read in the window finite; and, before and after the window, the program's
own count of the next batch's routing (`transformer_held_counts`): in every
layer the router's token-slots over ALL its experts sum to tokens x experts
per token, and the rows the held experts computed equal the slots routed to
them: nothing dropped."""

from __future__ import annotations

import functools
import math
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from yardstick import lm_gdn_flops, lm_kinds_flops, stats
from yardstick.generators.lm_kinds_train_step import update_limits
from yardstick.generators.lm_ssm_train_step import update_off_by
# `build`: (model, mesh, step, specs) of the cell; the scope reducers compile
# the step from it too
from yardstick.generators.lm_train_step import build
from yardstick.harness import annotate


def tied_routers(params, held: int, shares: int):
    """`params` with every router's columns of the first `held` experts
    repeated over all `shares` shares (see above): [d, held x shares]."""
    def tied(path, leaf):
        if getattr(path[-1], "key", None) != "w_router":
            return leaf
        if leaf.shape[-1] != held * shares:
            raise ValueError(f"a router of {leaf.shape[-1]} experts is not "
                             f"{shares} shares of {held}")
        return jnp.tile(leaf[..., :held], shares)
    return jax.tree_util.tree_map_with_path(tied, params)


def run(run) -> None:
    from tpu_mpi.models.transformer import (transformer_forward,
                                            transformer_held_counts,
                                            transformer_init)

    cfg, tr = run.config, run.traffic
    batch, seq = int(tr["batch"]), int(tr["seq"])
    pool, block_steps = int(tr["pool"]), int(tr["block_steps"])
    model, mesh, step, specs = build(run)
    shard = jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
    data = NamedSharding(mesh, P("dp", "sp"))

    # weights and tokens from the seed, on the device, one jitted call each
    key = jax.random.key(run.seed)
    shares = int(tr.get("router_shares", 0))

    def make_weights(k):
        params = transformer_init(k, model)
        return tied_routers(params, model.experts_held[1], shares) \
            if shares else params
    params = jax.jit(make_weights, out_shardings=shard)(
        jax.random.fold_in(key, 0))

    def make_tokens(k):
        tok = jax.random.randint(k, (pool, batch, seq), 0, model.vocab)
        return [(tok[i], jnp.roll(tok[i], -1, axis=1)) for i in range(pool)]
    batches = jax.jit(make_tokens, out_shardings=data)(
        jax.random.fold_in(key, 1))
    jax.block_until_ready((params, batches))
    run.phase("weights and tokens")

    compiled = step.lower(params, *batches[0]).compile()
    # one forward program gives the logits and counts the routing: the two
    # passes are one to the compiler, and one compile of eight layers less
    look = jax.jit(lambda p, tok: (transformer_forward(model, p, tok),
                                   transformer_held_counts(model, p, tok)))
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

    first, held = model.experts_held
    whole = batch * seq * model.experts_per_tok

    def routing():
        """The program's count of this moment's routing of the next batch,
        by layer, and whether nothing was dropped."""
        slots, did = (np.asarray(a) for a in look(
            state["params"], batches[state["i"] % pool][0])[1])
        here = slots[:, first:first + held].sum(axis=1)
        out = {"slots": slots.sum(axis=1).tolist(), "held": here.tolist(),
               "computed": did[:, 0].tolist(), "gathered": did[:, 1].tolist(),
               "fallbacks": did[:, 2].tolist(),
               "held_max_over_mean": [
                   float(row[first:first + held].max() * held / max(1, n))
                   for row, n in zip(slots, here)]}
        sound = bool((slots.sum(axis=1) == whole).all()
                     and (did[:, 0] == here).all())
        return out, sound

    # -- the reference's loss from the system's own parameters, then the step
    ref = run.cell.reference()
    # the reference re-lays three projections by the model's head counts
    relaid = types.SimpleNamespace(
        from_system=functools.partial(ref.from_system, model=cfg),
        make_grads_from=ref.make_grads_from)
    loss_from = ref.make_loss_from(cfg)
    nref, want, got = int(cfg["compare_steps"]), [], []
    for n in range(nref):
        tok, lab = batches[state["i"] % pool]
        loss, logits = loss_from(relaid.from_system(state["params"]), tok, lab,
                                 logits=n == 0)
        if n == 0:
            logits_off = float(off_by(look(state["params"], tok)[0], logits))
            del logits
            before = jax.device_get(state["params"])    # the step overwrites
        want.append(loss)
        got.append(block(1)[1])
        if n == 0:
            update_off = update_off_by(relaid, cfg, before, state["params"],
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
    routed0, sound0 = routing()
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
    routed1, sound1 = routing()

    q = stats.quartiles([t / block_steps for t in times])
    tokens_per_s = batch * seq / q["median"]
    run.row(f"train step [{batch} x {seq}] n={q['n']} blocks of {block_steps}  "
            f"per-step q1 {q['q1'] * 1e3:.3f} ms  median {q['median'] * 1e3:.3f} "
            f"ms  q3 {q['q3'] * 1e3:.3f} ms  spread {100 * q['spread']:.2f}%  "
            f"last loss {last:.4f}  per-step ms by block "
            + " ".join(f"{1e3 * t / block_steps:.1f}" for t in times))
    for label, routed, sound in (("before", routed0, sound0),
                                 ("after", routed1, sound1)):
        print(f"routing {label} the window, by layer: every router's "
              f"token-slots sum to {whole} and the held experts "
              f"[{first}, {first + held}) computed every slot routed to "
              f"them: {sound}; " + "  ".join(
                  f"{k} {v}" for k, v in routed.items()))
    computed = [n for r in (routed0, routed1) for n in r["computed"]]
    rows = sum(computed) / len(computed)        # a layer's, the mean
    shape = cfg["model"]
    run.results = {"metrics": {"train_tokens_per_s": tokens_per_s},
                   "correct": bool(correct and sound0 and sound1
                                   and not failed),
                   "attempted": steps, "failed": failed}
    run.facts = {"ops": steps, "per_op_s": q["median"],
                 "flops_per_step": lm_gdn_flops.flops_per_step(
                     shape, batch, seq, held_rows=rows),
                 "held_expert_flops_per_step": len(shape["mixer_kinds"]) *
                 lm_kinds_flops.held_expert_flops(shape, rows),
                 "held": {"begin": routed0, "end": routed1},
                 "scan": {"layers": lm_gdn_flops.layer_mixers(shape).count(
                              "gdn"),
                          "chunked_flops": lm_gdn_flops.scan_chunked_flops(
                              shape, batch, seq),
                          "least_bytes": lm_gdn_flops.scan_least_bytes(
                              shape, batch, seq,
                              jnp.dtype(shape["dtype"]).itemsize)}}
