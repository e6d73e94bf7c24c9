"""General generator of language-model training traffic: a trainer's loop
around the program's jitted train step, for a model that the configuration
file describes as data. The file's `model` block goes to the program's
`TransformerConfig` whole (with `max_seq` from the traffic), so another
architecture the program can express is another data file; its published
keys go to the plain reference. The traffic file gives the token batches:

  batch, seq    tokens per step = batch x seq
  pool          that many seeded token batches, made on the device once and
                cycled (a data loader that is never the bottleneck)
  block_steps   steps between two loss readbacks; steps are chained through
                `params`, which the step is given to overwrite (`donate`),
                so a block's wall time is its steps' time

One sample per block: (first dispatch -> the block's loss on the host) /
block_steps; `train_tokens_per_s` = batch x seq / the median. Correctness,
all of it outside the window: before each of the first `compare_steps` steps
the plain reference (reference/lm_train_step.py) computes that step's loss in
float32 from the system's own parameters at that moment, one layer's weights
at a time, and the step's loss must agree within the file's tolerance; before
the first step the program's logits for that batch (`transformer_forward`)
must agree with the reference's within `logits_tolerance`, as the root mean
square of the difference over that of the reference's logits (a mean loss at
random weights sits near ln(vocab) whatever the weights are; 400 million
logits do not); the **update** of the first step, made by the timed
executable itself, is held to the reference's gradient (`update_off_by`,
below); before and after the window the program's own count of token-slots
per expert (`transformer_expert_counts`) must sum, in every layer, to tokens
x experts per token (true by construction of `moe_dropless`, which hands the
experts these group sizes and tokens x k rows: a check of the counter; a slot
that was lost in the computation shows in the logits and in the update);
every loss read in the window must be finite."""

from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from yardstick import lm_flops, stats
from yardstick.harness import annotate


def build(run):
    """(model, mesh, step, specs) of the cell: the program's config from the
    file's `model` block, and its jitted train step on the file's mesh."""
    from tpu_mpi import xla
    from tpu_mpi.models.transformer import (TransformerConfig,
                                            transformer_train_step)
    cfg = run.config
    fields = dict(cfg["model"], max_seq=int(run.traffic["seq"]))
    fields["dtype"] = jnp.dtype(fields["dtype"])
    model = TransformerConfig(**fields)
    mesh = xla.make_mesh(dict(cfg["mesh"]), devices=run.devices)
    step, specs = transformer_train_step(model, mesh, lr=cfg["lr"], donate=True)
    return model, mesh, step, specs


def update_off_by(ref, cfg: dict, n_heads: int, before, after, tokens,
                  labels) -> dict:
    """How far one step of the system moved its parameters from where the
    reference's gradient sends them, by leaf (the reference's names, the
    worst layer): `before` are the parameters the step was given (a copy;
    on the host is fine), `after` what it returned. With g the reference's
    float32 gradient at `before` (one layer at a time) and want = before -
    lr x g rounded to the parameters' dtype, as the step rounds it, a leaf
    reads sum (after - want)^2 / sum (want - before)^2: the share of the
    expected update's energy by which the system's parameters miss it. No
    update at all, a doubled learning rate or a leaf whose gradient never
    arrived read 1 or more. bfloat16 parameters under plain SGD mostly do
    not move (lr x g is far below half a unit in the last place of most
    weights) and the ones that do, move by a whole unit, so there the
    reading is, to first order, sum ulp x |the gradients' difference| / sum
    ulp x |g|: a weighted relative error of the system's gradient, of which
    a step's rounding shows nothing more. Where the reference moves nothing
    (norm scales at one) the system must move nothing, else inf."""
    lr = float(cfg["lr"])

    @jax.jit
    def sums(b, a, g):
        # rounded by `reduce_precision`: the compiler may drop a conversion
        # to the parameters' dtype and back (the v5e's does, PR 25)
        kind = jnp.finfo(b.dtype)
        b32 = b.astype(jnp.float32)
        want = lax.reduce_precision(b32 - lr * g, kind.nexp, kind.nmant)
        return (jnp.sum(jnp.square(a.astype(jnp.float32) - want)),
                jnp.sum(jnp.square(want - b32)))

    before = ref.from_system(before, n_heads)
    after = ref.from_system(after, n_heads)
    off = {}
    for i, grads in ref.make_grads_from(cfg)(before, tokens, labels):
        b, a = (before, after) if i is None else \
            (before["layers"][i], after["layers"][i])
        for name, g in grads.items():
            missed, moved = (float(v) for v in sums(b[name], a[name], g))
            ratio = missed / moved if moved else math.inf if missed else 0.0
            off[name] = max(off.get(name, 0.0), ratio)
    return off


def run(run) -> None:
    from tpu_mpi.models.transformer import (transformer_expert_counts,
                                            transformer_forward,
                                            transformer_init)

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
    count = jax.jit(lambda p, tok: transformer_expert_counts(model, p, tok))
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

    def slots():
        """The program's count of this moment's routing of the next batch,
        and whether every layer's slots are all there."""
        counts = np.asarray(count(state["params"], batches[state["i"] % pool][0]))
        whole = batch * seq * model.experts_per_tok
        return counts, bool((counts.sum(axis=1) == whole).all())

    # -- the reference's loss from the system's own parameters, then the step
    ref = run.cell.reference()
    loss_from = ref.make_loss_from(cfg)
    nref, want, got = int(cfg["compare_steps"]), [], []
    for n in range(nref):
        tok, lab = batches[state["i"] % pool]
        loss, logits = loss_from(
            ref.from_system(state["params"], model.n_heads), tok, lab)
        if n == 0:
            logits_off = float(off_by(forward(state["params"], tok), logits))
            before = jax.device_get(state["params"])    # the step overwrites
        del logits
        want.append(loss)
        got.append(block(1)[1])
        if n == 0:
            update_off = update_off_by(ref, cfg, model.n_heads, before,
                                       state["params"], tok, lab)
            del before
            run.phase("reference loss, first step, reference gradient")
    run.phase("reference losses and first steps")
    tol, ltol = float(cfg["loss_tolerance"]), float(cfg["logits_tolerance"])
    utol = float(cfg["update_tolerance"])
    worst = max(abs(g - w) for g, w in zip(got, want))
    print(f"first losses: system {got}  reference {want}  "
          f"worst |diff| {worst:.3e} (tolerance {tol})  logits off by "
          f"{logits_off:.3e} of their rms (tolerance {ltol})")
    print(f"first update off by, the worst layer's share of the expected "
          f"update's energy (tolerance {utol}): " + "  ".join(
              f"{k} {v:.3e}" for k, v in update_off.items()))
    correct = all(math.isfinite(g) for g in got) and worst <= tol \
        and logits_off <= ltol and max(update_off.values()) <= utol
    counts0, whole0 = slots()
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
    counts1, whole1 = slots()

    q = stats.quartiles([t / block_steps for t in times])
    tokens_per_s = batch * seq / q["median"]
    mean = batch * seq * model.experts_per_tok / model.n_experts
    run.row(f"train step [{batch} x {seq}] n={q['n']} blocks of {block_steps}  "
            f"per-step q1 {q['q1'] * 1e3:.3f} ms  median {q['median'] * 1e3:.3f} "
            f"ms  q3 {q['q3'] * 1e3:.3f} ms  spread {100 * q['spread']:.2f}%  "
            f"last loss {last:.4f}")
    for label, counts, whole in (("before", counts0, whole0),
                                 ("after", counts1, whole1)):
        print(f"token-slots per expert {label} the window: every layer sums to "
              f"{batch * seq * model.experts_per_tok}: {whole}; busiest / mean "
              f"by layer " + " ".join(f"{c.max() / mean:.3f}" for c in counts))
    run.results = {"metrics": {"train_tokens_per_s": tokens_per_s},
                   "correct": bool(correct and whole0 and whole1 and not failed),
                   "attempted": steps, "failed": failed}
    run.facts = {"ops": steps, "per_op_s": q["median"],
                 "flops_per_step": lm_flops.flops_per_step(
                     cfg["model"], batch, seq),
                 "expert_flops_per_step": model.n_layers *
                 lm_flops.expert_flops_per_layer(cfg["model"], batch, seq),
                 "expert_counts": {"begin": counts0.tolist(),
                                   "end": counts1.tolist()}}
