"""Elastic DDP training: bucketed-overlap gradient Allreduce + grow-back.

Data-parallel training of the toy transformer (tpu_mpi/models) on the
host-path training tier (docs/training.md): JAX computes loss and
gradients on each rank's own batch; ``tpu_mpi.train.DDPTrainer`` streams
the gradients, in reverse-layer order, through size-bounded buckets
riding persistent Allreduce handles — each bucket Started the moment its
last gradient lands, Waited just-in-time at the optimizer fold.

On top of the perf story sits the elastic one: every step checkpoints the
packed optimizer state sharded 1/nranks (PR 8 CRC'd format).  When a rank
dies mid-step the survivors revoke, shrink, ``Comm_spawn`` a replacement,
``Intercomm_merge`` it back, and EVERY rank (old and new) reloads from the
checkpoint — resharding across the new world — and keeps training.  The
batch for (step, rank) is seeded by (step, rank), so the mean gradient is
a fixed SET of per-rank contributions regardless of which process landed
on which rank after the resize: the loss curve is **bitwise identical**
to an uninterrupted run (rank 0 prints each loss as a float64 hex).

Run (no failure, thread tier):
    tpurun --sim 4 examples/14-ddp-train.py

Run with an injected failure at step 3 (procs tier, real SIGKILL):
    TPU_MPI_HEARTBEAT_MS=100 TPU_MPI_TRAIN_KILL_STEP=3 \
        tpurun -n 4 --procs --sim 1 examples/14-ddp-train.py

On the thread tier ranks are threads of ONE process, so a real SIGKILL
would take down the whole job; there the same knob injects the
failure-detector verdict instead (``ctx.peer_failed`` — exactly what the
heartbeat timeout produces on the procs tier) and the victim thread steps
out through the same typed-error recovery path.
"""

import os
import signal

import jax
import jax.numpy as jnp
import numpy as np

import tpu_mpi as MPI
from tpu_mpi.error import ProcFailedError, RevokedError
from tpu_mpi.models.transformer import (
    TransformerConfig, _xent, transformer_forward, transformer_init)
from tpu_mpi.train import DDPTrainer

CFG = TransformerConfig(vocab=64, d_model=32, n_heads=2, n_layers=2,
                        d_ff=64, max_seq=32)
BATCH, SEQ = 4, 16
STEPS = int(os.environ.get("TPU_MPI_TRAIN_STEPS", "6"))
KILL_STEP = int(os.environ.get("TPU_MPI_TRAIN_KILL_STEP", "-1"))
KILL_RANK = int(os.environ.get("TPU_MPI_TRAIN_KILL_RANK", "1"))
LAYER_KEYS = ("ln1", "w_qkv", "w_proj", "ln2", "w_in", "w_out")


def flatten(tree):
    """transformer_init's nested params -> flat name->array dict in
    forward order (the trainer feeds grads in reversed(dict) order)."""
    flat = {"embed": tree["embed"]}
    for i, layer in enumerate(tree["layers"]):
        for k in LAYER_KEYS:
            flat[f"layers.{i}.{k}"] = layer[k]
    flat["ln_f"] = tree["ln_f"]
    return flat


def unflatten(flat):
    """Trainer's float64 masters -> the float32 pytree the forward takes."""
    as_f32 = lambda a: jnp.asarray(a, jnp.float32)          # noqa: E731
    return {"embed": as_f32(flat["embed"]),
            "ln_f": as_f32(flat["ln_f"]),
            "layers": [{k: as_f32(flat[f"layers.{i}.{k}"])
                        for k in LAYER_KEYS}
                       for i in range(CFG.n_layers)]}


@jax.jit
def loss_and_grads(params, tokens, labels):
    def loss_fn(p):
        return _xent(transformer_forward(CFG, p, tokens), labels)
    return jax.value_and_grad(loss_fn)(params)


def batch_for(step, rank):
    """The (step, rank) batch.  Seeded by the RANK SLOT, not the process:
    after a resize the slots are re-dealt, but the set of per-rank
    contributions — and so the rank-ordered Allreduce — is unchanged."""
    rng = np.random.default_rng(1_000_003 * step + rank)
    toks = rng.integers(0, CFG.vocab, size=(BATCH, SEQ + 1))
    return (np.asarray(toks[:, :-1], dtype=np.int32),
            np.asarray(toks[:, 1:], dtype=np.int32))


def build_trainer(comm):
    params = flatten(transformer_init(jax.random.PRNGKey(0), CFG))
    return DDPTrainer(params, comm, lr=0.5, momentum=0.9,
                      bucket_bytes=1 << 14)


def die(comm, world_rank):
    if os.environ.get("TPU_MPI_PROC_RANK") is not None:
        os.kill(os.getpid(), signal.SIGKILL)    # procs tier: the real thing
    # thread tier: deliver the detector verdict by hand and leave through
    # the same typed error the surviving ranks will see
    comm.ctx.peer_failed(world_rank)
    raise ProcFailedError("injected failure (thread-tier SIGKILL analog)",
                          ranks=(world_rank,))


def main():
    MPI.Init()
    parent = MPI.Comm_get_parent()
    replacement = parent is not MPI.COMM_NULL
    if replacement:
        comm = MPI.Intercomm_merge(parent, True)
        ckpt = MPI.bcast(None, 0, comm)          # survivors know the path
        world_rank = -1                          # never a kill victim
    else:
        comm = MPI.COMM_WORLD
        world_rank = comm.rank()
        ckpt = os.environ.get(
            "TPU_MPI_TRAIN_CKPT", f"/tmp/ddp-train-{os.getppid()}.ckpt")
    FULL = comm.size()

    trainer = build_trainer(comm)
    step = trainer.load(ckpt) if replacement else 0
    losses = []
    while step < STEPS:
        try:
            if step == KILL_STEP and world_rank == KILL_RANK:
                die(comm, world_rank)
            tokens, labels = batch_for(step, comm.rank())
            loss, grads = loss_and_grads(unflatten(trainer.params),
                                         tokens, labels)
            gflat = flatten(grads)
            trainer.step((name, np.asarray(gflat[name]))
                         for name in reversed(list(gflat)))
            lsum = MPI.Allreduce(np.array([float(loss)]), MPI.SUM, comm)
            mean = float(lsum[0]) / comm.size()
            losses.append(mean)
            if comm.rank() == 0:
                print(f"step {step} loss {mean:.4f} "
                      f"hex {np.float64(mean).hex()}", flush=True)
            trainer.save(ckpt)                   # sharded 1/nranks, CRC'd
            step += 1
        except (ProcFailedError, RevokedError) as e:
            print(f"rank {world_rank}: {type(e).__name__} at step {step} — "
                  f"revoke, shrink, grow back, reshard", flush=True)
            MPI.Comm_revoke(comm)
            comm = MPI.Comm_shrink(comm)
            if comm is MPI.COMM_NULL:            # not a survivor
                MPI.Finalize()
                return
            inter = MPI.Comm_spawn(__file__, None, FULL - comm.size(), comm)
            comm = MPI.Intercomm_merge(inter, False)
            MPI.bcast(ckpt, 0, comm)             # replacements need the path
            trainer = build_trainer(comm)        # fresh handles on the new comm
            step = trainer.load(ckpt)            # reshard: resume bitwise
            continue

    MPI.Barrier(comm)
    if comm.rank() == 0:
        print(f"trained {STEPS} steps on {comm.size()} rank(s); loss "
              f"{losses[0]:.4f} -> {losses[-1]:.4f}, overlap fraction "
              f"{trainer.overlap_fraction():.2f}", flush=True)
        assert losses[-1] < losses[0], "loss did not decrease"
    print(f"OK-{world_rank if not replacement else 'spawned'}", flush=True)
    MPI.Finalize()


main()
