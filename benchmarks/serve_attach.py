"""Serve-tier attach latency vs the Init cold start it replaces.

The serve tier's pitch (docs/serving.md) is quantified here:

- **attach** — the full client-side `serve.attach()` round trip against a
  warm broker on loopback TCP: socket connect, HELLO, broker-side lease
  grant (token check, namespace carve, root-cid alloc), LEASE back. One
  distribution over many attach/detach cycles (each on a fresh tenant id,
  as real clients would).
- **first_op** — attach + one 8-element Allreduce: the time to *useful
  work* for a new tenant on the warm pool.
- **cold_init** — the baseline being replaced: a fresh Python process
  doing `import tpu_mpi; MPI.Init()` + the same Allreduce via `spmd_run`
  on a world of the same size (full interpreter + jax + Init cold start).

The acceptance gate (ISSUE 9 / CI serve smoke job) is attach p50 < 1 ms.

Run:
    python benchmarks/serve_attach.py [--attaches 100] [--cold-reps 3]
        [--nranks 4] [--json benchmarks/results/serve-attach-cpusim.json]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np


def percentiles(samples_s: list) -> dict:
    xs = sorted(samples_s)
    at = lambda q: xs[min(len(xs) - 1, int(q * len(xs)))]
    return {"n": len(xs), "p50_ms": at(0.50) * 1e3, "p90_ms": at(0.90) * 1e3,
            "p99_ms": at(0.99) * 1e3, "min_ms": xs[0] * 1e3,
            "max_ms": xs[-1] * 1e3}


def bench_attach(broker, n: int) -> tuple[dict, dict]:
    from tpu_mpi import serve
    attach_s, first_op_s = [], []
    # one throwaway cycle absorbs client-side import/jit one-offs
    serve.attach(broker.address, tenant="warmup").detach()
    x = np.ones(8, np.float32)
    for i in range(n):
        t0 = time.perf_counter()
        s = serve.attach(broker.address, tenant=f"bench{i}")
        t1 = time.perf_counter()
        out = s.allreduce(x)
        t2 = time.perf_counter()
        assert out[0] == broker.pool.nranks
        s.detach()
        attach_s.append(t1 - t0)
        first_op_s.append(t2 - t0)
    return percentiles(attach_s), percentiles(first_op_s)


_COLD_SCRIPT = """
import os, sys, time
sys.path.insert(0, {repo!r})
os.environ.setdefault("JAX_PLATFORMS", "cpu")
t0 = time.perf_counter()
import numpy as np
import tpu_mpi as MPI
from tpu_mpi._runtime import spmd_run

def body():
    MPI.Init()
    out = MPI.Allreduce(np.ones(8, np.float32), MPI.SUM, MPI.COMM_WORLD)
    assert out[0] == MPI.Comm_size(MPI.COMM_WORLD)
    MPI.Finalize()

spmd_run(body, {nranks})
print(time.perf_counter() - t0)
"""


def bench_cold_init(nranks: int, reps: int) -> dict:
    samples = []
    env = dict(os.environ)
    env.pop("TPU_MPI_PROC_RANK", None)
    for _ in range(reps):
        res = subprocess.run(
            [sys.executable, "-c",
             _COLD_SCRIPT.format(repo=_REPO, nranks=nranks)],
            capture_output=True, text=True, timeout=300, env=env)
        assert res.returncode == 0, res.stderr
        samples.append(float(res.stdout.strip().splitlines()[-1]))
    return percentiles(samples)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--attaches", type=int, default=100)
    ap.add_argument("--cold-reps", type=int, default=3)
    ap.add_argument("--nranks", type=int, default=4)
    ap.add_argument("--json", default=None,
                    help="write results JSON here (e.g. "
                         "benchmarks/results/serve-attach-cpusim.json)")
    args = ap.parse_args()

    from tpu_mpi import serve
    broker = serve.Broker(nranks=args.nranks)
    broker.run_in_thread()
    t_warm = time.time()
    attach, first_op = bench_attach(broker, args.attaches)
    broker.close()

    cold = bench_cold_init(args.nranks, args.cold_reps)
    speedup = cold["p50_ms"] / attach["p50_ms"]

    result = {
        "benchmark": "serve-attach",
        "substrate": "cpu-sim",
        "nranks": args.nranks,
        "transport": "loopback-tcp",
        "attach": attach,
        "attach_plus_first_allreduce": first_op,
        "cold_init_baseline": cold,
        "cold_over_attach_p50": speedup,
        "gate": {"attach_p50_under_ms": 1.0,
                 "passed": attach["p50_ms"] < 1.0},
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S", time.localtime(t_warm)),
    }
    print(f"attach            p50 {attach['p50_ms']:8.3f} ms   "
          f"p90 {attach['p90_ms']:8.3f} ms   p99 {attach['p99_ms']:8.3f} ms")
    print(f"attach+allreduce  p50 {first_op['p50_ms']:8.3f} ms   "
          f"p90 {first_op['p90_ms']:8.3f} ms")
    print(f"cold Init+op      p50 {cold['p50_ms']:8.1f} ms   "
          f"({speedup:,.0f}x slower than attach)")
    print(f"gate attach p50 < 1 ms: "
          f"{'PASS' if result['gate']['passed'] else 'FAIL'}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(result, f, indent=2)
        print(f"wrote {args.json}")
    return 0 if result["gate"]["passed"] else 1


if __name__ == "__main__":
    sys.exit(main())
