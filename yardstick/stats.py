"""Arithmetic of the yardstick: order statistics over the window's samples
and the closed forms that turn shapes into bytes and FLOPs. No JAX here, so
the tests pin every formula on fixed inputs.

Medians and quartiles over every sample of the window, never a best-of."""

from __future__ import annotations

import math
from typing import Mapping, Sequence


def percentile(samples: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100), linear interpolation between order
    statistics (numpy's default). An empty sample set is an error: a
    metric is left out by its reader, never reported as 0."""
    xs = sorted(float(x) for x in samples)
    if not xs:
        raise ValueError("percentile of no samples")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} outside 0..100")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(samples: Sequence[float]) -> float:
    return percentile(samples, 50.0)


def quartiles(samples: Sequence[float]) -> dict:
    """What is printed beside every median: n, the quartiles and the spread
    (distance between the quartiles over the median)."""
    q1, q2, q3 = (percentile(samples, q) for q in (25.0, 50.0, 75.0))
    return {"n": len(samples), "q1": q1, "median": q2, "q3": q3,
            "spread": (q3 - q1) / q2 if q2 else float("inf")}


def geomean(values: Sequence[float]) -> float:
    """Geometric mean (a ladder's metric is the geomean of its per-size
    medians, so no one size decides it)."""
    vs = [float(v) for v in values]
    if not vs or any(v <= 0.0 for v in vs):
        raise ValueError(f"geometric mean needs positive values, got {vs}")
    return math.exp(sum(math.log(v) for v in vs) / len(vs))


def coll_algbw_gbps(payload_bytes: int, per_op_seconds: float) -> float:
    """Algorithm bandwidth: one rank's payload bytes over the time of one
    op, in GB/s of 1e9 bytes."""
    return payload_bytes / per_op_seconds / 1e9


def fold_bytes(nranks: int, payload_bytes: int) -> int:
    """Least HBM traffic of one rank-ordered fold of n operands into a new
    result: n reads and one write of the payload."""
    return (nranks + 1) * payload_bytes


def transformer_flops_per_step(cfg: Mapping[str, int], batch: int,
                               seq: int) -> float:
    """Matrix-multiply FLOPs of one train step of the flagship (forward and
    backward, backward = 2 x forward; recomputation is not counted). Copied
    from benchmarks/flagship_probe.py:model_flops_per_step, with the sizes
    as arguments. The causal scores are counted as a full seq x seq matrix,
    as the model computes them."""
    b, t = int(batch), int(seq)
    d, f, v = int(cfg["d_model"]), int(cfg["d_ff"]), int(cfg["vocab"])
    per_layer = (2 * b * t * d * 3 * d        # qkv
                 + 2 * 2 * b * t * t * d      # scores + pv
                 + 2 * b * t * d * d          # proj
                 + 2 * 2 * b * t * d * f)     # ffn in/out
    fwd = int(cfg["n_layers"]) * per_layer + 2 * b * t * d * v   # + logits
    return 3.0 * fwd


#: Largest integer a chain may reach and still be exact, by dtype: every
#: integer up to 2**(mantissa bits + 1) is representable.
EXACT_INT_BOUND = {"float32": 2 ** 24, "bfloat16": 2 ** 8,
                   "float16": 2 ** 11, "int32": 2 ** 31 - 1}


def chain_ops_bound(dtype: str, nranks: int) -> int:
    """How many chained allreduce(SUM) ops of 0/1 operands stay exact:
    after k ops an element is at most 1 + k(n-1)."""
    return (EXACT_INT_BOUND[dtype] - 1) // max(nranks - 1, 1)
