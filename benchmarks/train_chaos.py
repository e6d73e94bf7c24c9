"""Bounded chaos run for the training tier: SIGKILL mid-step, grow back,
loss curve bitwise-identical.

Three launches of ``examples/14-ddp-train.py`` (the elastic DDP example),
all on 4 ranks for the same step budget:

1. **reference** — thread tier, no failure.  Captures the per-step loss
   curve as float64 hex (rank 0 prints ``step k loss ... hex <hex>``).
2. **thread-tier chaos** — same run with a failure injected at
   ``--kill-step`` (on the thread tier ranks are threads, so the kill is
   the failure-detector verdict — the same typed-error path the real
   SIGKILL produces).  Survivors revoke, shrink, ``Comm_spawn`` a
   replacement, merge, reload the sharded checkpoint and keep training.
3. **procs-tier chaos** — a real ``SIGKILL`` of a rank process mid-run;
   the launcher reports ``EXIT_SHRUNK_OK`` (66: a rank died by signal,
   every survivor — and here the replacement — finished clean).

Asserted, each with a bounded wall clock:

- every run prints all STEPS loss lines and the final ``trained ... on 4
  rank(s)`` banner (full size restored);
- both chaos runs actually resized (recovery banner + ``OK-spawned``);
- the loss-hex curve of BOTH chaos runs is **bitwise identical** to the
  reference (last print per step wins: the killed step is retried).

Exit codes: ``EXIT_RESIZED_OK`` (67) — ranks were lost and fully
restored, curves bitwise; ``1`` — any failed assertion.

Run:
    python benchmarks/train_chaos.py [--steps 6] [--kill-step 3]
        [--budget 420]
"""

from __future__ import annotations

import argparse
import os
import re
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

_LOSS = re.compile(r"^step (\d+) loss \S+ hex (\S+)$", re.M)


def _launch(tag: str, argv: list, env: dict, timeout: float) -> "subprocess.CompletedProcess":
    full = dict(os.environ)
    for k in ("TPU_MPI_PROC_RANK", "TPU_MPI_TRAIN_KILL_STEP",
              "TPU_MPI_TRAIN_CKPT"):
        full.pop(k, None)
    full["JAX_PLATFORMS"] = "cpu"
    full["PYTHONPATH"] = _REPO + os.pathsep + full.get("PYTHONPATH", "")
    full.update(env)
    t0 = time.monotonic()
    res = subprocess.run(
        [sys.executable, "-m", "tpu_mpi.launcher"] + argv
        + [os.path.join(_REPO, "examples", "14-ddp-train.py")],
        capture_output=True, text=True, timeout=timeout, env=full, cwd=_REPO)
    print(f"{tag}: rc={res.returncode} in {time.monotonic() - t0:.1f}s",
          file=sys.stderr)
    return res


def _curve(stdout: str, steps: int) -> list:
    """step -> loss hex, LAST print per step (the killed step is retried
    after the resize and must reproduce the same value)."""
    got = {}
    for m in _LOSS.finditer(stdout):
        got[int(m.group(1))] = m.group(2)
    assert sorted(got) == list(range(steps)), f"loss lines missing: {sorted(got)}"
    return [got[s] for s in range(steps)]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--kill-step", type=int, default=3)
    ap.add_argument("--budget", type=float, default=420.0,
                    help="wall-clock bound per launch (s)")
    args = ap.parse_args()

    from tpu_mpi.launcher import EXIT_RESIZED_OK, EXIT_SHRUNK_OK

    base = {"TPU_MPI_TRAIN_STEPS": str(args.steps)}
    kill = dict(base, TPU_MPI_TRAIN_KILL_STEP=str(args.kill_step))
    banner = f"trained {args.steps} steps on 4 rank(s)"

    ref = _launch("reference (threads)", ["--sim", "4"], base, args.budget)
    assert ref.returncode == 0, (ref.returncode, ref.stderr)
    assert banner in ref.stdout, ref.stdout
    curve = _curve(ref.stdout, args.steps)
    print("reference curve: " + " ".join(curve), file=sys.stderr)

    tch = _launch("chaos (threads)", ["--sim", "4"],
                  dict(kill, TPU_MPI_TRAIN_CKPT=f"/tmp/train-chaos-t-{os.getpid()}.ckpt"),
                  args.budget)
    assert tch.returncode == 0, (tch.returncode, tch.stderr)
    assert "revoke, shrink, grow back, reshard" in tch.stdout, tch.stdout
    assert "OK-spawned" in tch.stdout, tch.stdout
    assert banner in tch.stdout, tch.stdout           # full size restored
    assert _curve(tch.stdout, args.steps) == curve, "thread-tier curve diverged"

    pch = _launch("chaos (procs, SIGKILL)",
                  ["-n", "4", "--procs", "--sim", "1",
                   "--timeout", str(args.budget - 30)],
                  dict(kill, TPU_MPI_HEARTBEAT_MS="100",
                       TPU_MPI_FAILURE_TIMEOUT_MS="1500",
                       TPU_MPI_TRAIN_CKPT=f"/tmp/train-chaos-p-{os.getpid()}.ckpt"),
                  args.budget)
    assert pch.returncode == EXIT_SHRUNK_OK, (pch.returncode, pch.stdout,
                                              pch.stderr)
    assert "(signal SIGKILL)" in pch.stderr, pch.stderr
    assert "OK-spawned" in pch.stdout, pch.stdout
    assert banner in pch.stdout, pch.stdout
    assert _curve(pch.stdout, args.steps) == curve, "procs-tier curve diverged"

    print("ranks lost and fully restored on both tiers; loss curves "
          "bitwise-identical to the uninterrupted reference", file=sys.stderr)
    return EXIT_RESIZED_OK


if __name__ == "__main__":
    sys.exit(main())
