"""General generator of training traffic: a trainer's loop around the
program's jitted train step. The configuration file gives the model and the
mesh, the traffic file the token batches:

  batch, seq    tokens per step = batch x seq
  pool          that many seeded token batches, made on the device once and
                cycled (a data loader that is never the bottleneck)
  block_steps   steps between two loss readbacks; steps are chained through
                `params`, so a block's wall time is its steps' time

One sample per block: (first dispatch -> the block's loss on the host) /
block_steps. A non-finite loss fails the block's steps. Before the window
the loss of the first `compare_steps` steps is compared with the plain
reference (reference/train_step.py) run from the same weights on the same
batches; the tolerance and its reason are in the configuration file."""

from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from yardstick import stats
from yardstick.harness import annotate


def run(run) -> None:
    from tpu_mpi import xla
    from tpu_mpi.models.transformer import (TransformerConfig,
                                            transformer_init,
                                            transformer_train_step)

    cfg, tr = run.config, run.traffic
    batch, seq = int(tr["batch"]), int(tr["seq"])
    pool, block_steps = int(tr["pool"]), int(tr["block_steps"])
    model = TransformerConfig(
        vocab=cfg["vocab"], d_model=cfg["d_model"], n_heads=cfg["n_heads"],
        n_layers=cfg["n_layers"], d_ff=cfg["d_ff"], max_seq=seq,
        dtype=jnp.dtype(cfg["dtype"]))
    mesh = xla.make_mesh(dict(cfg["mesh"]), devices=run.devices)
    step, specs = transformer_train_step(model, mesh, lr=cfg["lr"])
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

    # -- the plain reference's first losses, then the system's --------------
    nref = int(cfg["compare_steps"])
    want = run.cell.reference().losses(
        model.n_heads, cfg["lr"], params, batches[:nref],
        micro=int(cfg["reference_micro_batch"]))
    run.phase("reference steps")
    run.memory_row("after the reference's steps")
    compiled = step.lower(params, *batches[0]).compile()
    run.phase("step executable")
    state = {"params": params, "i": 0}

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

    got = [block(1)[1] for _ in range(nref)]
    tol = float(cfg["loss_tolerance"])
    worst = max(abs(g - w) for g, w in zip(got, want))
    print(f"first losses: system {got}  reference {want}  "
          f"worst |diff| {worst:.3e} (tolerance {tol})")
    correct = all(math.isfinite(g) for g in got) and worst <= tol
    block(block_steps)                      # one block as measured

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
    run.results = {"metrics": {"train_tokens_per_s": tokens_per_s},
                   "correct": bool(correct and not failed),
                   "attempted": steps, "failed": failed}
    run.facts = {"ops": steps, "per_op_s": q["median"],
                 "flops_per_step": stats.transformer_flops_per_step(
                     cfg, batch, seq)}
