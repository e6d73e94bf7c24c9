"""Request-scoped trace context: the distributed-tracing spine of the serve
tier (docs/observability.md "Request traces").

A :class:`TraceCtx` is three fields — a 64-bit ``trace_id``, the parent
``span_id``, and the sampling bit — minted in ``serve/session.py`` when a
sampled op starts, carried across every hop in the frame metadata
(``meta["trace"] = {"id", "span", "s"}``), and bound to a thread-local slot
on the serving side so the front-door worker, the fair-queue dispatcher, and
the per-rank pvar op-scope can each open child spans without plumbing an
argument through every call signature.

Spans land in one process-global bounded buffer as plain dicts::

    {"trace": id, "span": sid, "parent": psid, "name": "...",
     "who": "client" | "router" | "broker" | "rank 3" | ...,
     "t0": monotonic, "t1": monotonic, "status": "ok" | "error", ...}

The same buffer holds the span tree of every host-path collective of an
SPMD run (docs/observability.md "Op spans"): with ``trace_sample > 0`` and
no request context bound, ``perfvars.op_end`` publishes one compact record
per op (:func:`emit_op`) that :func:`drain` expands into the dicts above,
so a 1 kHz op pays one tuple, not nine dicts. Their ids are a function of
``(cid, round, rank)``, so every rank names the same round alike and a
late child (the watcher's ``*.done`` spans) finds its parent unaided.

Op trees are kept apart from request spans, under a policy of their own:
what came FIRST stays. Once ``_OP_SPAN_CAP`` spans of them are held, later
ones are refused and counted (``dropped``), so a profiled interval early in
a long run survives the run. Request spans keep their ring (the oldest
quarter goes once ``_SPAN_CAP`` are held): a long-lived serve process
always has its most recent requests.

``analyze/timeline.py`` renders the buffer as Chrome-trace slices (one lane
per ``who``); multi-process runs dump per process via :func:`dump_spans`
and merge offline.

Overhead discipline matches ``analyze/events.enabled()``: an unsampled run
pays one tuple compare against ``config.GENERATION`` per op — no id
minting, no TLS writes, no metadata key.
"""

from __future__ import annotations

import itertools
import json
import os
import random
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from . import config
from . import locksmith

_UNSET = object()
_rate_cache: Tuple[Any, float] = (_UNSET, 0.0)


def sample_rate() -> float:
    """The effective TPU_MPI_TRACE_SAMPLE rate — cached on
    ``config.GENERATION`` so the untraced hot path is one tuple compare."""
    global _rate_cache
    cached_gen, val = _rate_cache
    if cached_gen == config.GENERATION:
        return val
    val = float(config.load().trace_sample)
    _rate_cache = (config.GENERATION, val)
    return val


def enabled() -> bool:
    """Whether request tracing can sample at all (rate > 0)."""
    return sample_rate() > 0.0


def sample() -> bool:
    """One sampling decision at trace-birth time (client session op)."""
    rate = sample_rate()
    if rate <= 0.0:
        return False
    if rate >= 1.0:
        return True
    return random.random() < rate


# span-id minting: a per-process nonce + counter keeps ids unique across
# the processes one trace crosses without coordination.
_NONCE = os.urandom(3).hex()
_ids = itertools.count(1)


def _new_id() -> str:
    return f"{_NONCE}-{next(_ids)}"


new_id = _new_id


class TraceCtx:
    """One request's position in its trace: where a child span attaches."""

    __slots__ = ("trace_id", "span_id", "sampled")

    def __init__(self, trace_id: str, span_id: str, sampled: bool = True):
        self.trace_id = trace_id
        self.span_id = span_id
        self.sampled = sampled

    @classmethod
    def mint(cls) -> "TraceCtx":
        """A fresh root context (trace birth, client side)."""
        return cls(os.urandom(8).hex(), _new_id(), True)

    def child(self) -> "TraceCtx":
        """A context one span deeper (the receiver side of a hop)."""
        return TraceCtx(self.trace_id, _new_id(), self.sampled)

    def to_meta(self) -> dict:
        """The compact frame-metadata carriage of this context."""
        return {"id": self.trace_id, "span": self.span_id,
                "s": 1 if self.sampled else 0}

    @classmethod
    def from_meta(cls, meta: Optional[dict]) -> Optional["TraceCtx"]:
        """Recover a context from frame metadata (None when untraced)."""
        t = (meta or {}).get("trace")
        if not isinstance(t, dict) or "id" not in t or "span" not in t:
            return None
        return cls(str(t["id"]), str(t["span"]), bool(t.get("s", 1)))

    def __repr__(self) -> str:
        return f"<TraceCtx {self.trace_id}/{self.span_id}>"


# ---------------------------------------------------------------------------
# Thread-local binding: the serving side's implicit context slot.
# ---------------------------------------------------------------------------

_tls = threading.local()


def current() -> Optional[TraceCtx]:
    """The TraceCtx bound to this thread (None when untraced)."""
    return getattr(_tls, "ctx", None)


class bind:
    """Context manager binding ``ctx`` (may be None) to this thread."""

    __slots__ = ("_ctx", "_prev")

    def __init__(self, ctx: Optional[TraceCtx]):
        self._ctx = ctx

    def __enter__(self) -> Optional[TraceCtx]:
        self._prev = getattr(_tls, "ctx", None)
        _tls.ctx = self._ctx
        return self._ctx

    def __exit__(self, *exc) -> bool:
        _tls.ctx = self._prev
        return False


# ---------------------------------------------------------------------------
# Span buffer: process-global, bounded, drained by timeline export.
# ---------------------------------------------------------------------------

_SPAN_CAP = 8192              # request spans: a ring
#: op trees: sized for one 15 s window of 8 B ops, 4 ranks x 1 kHz x 9 spans
_OP_SPAN_CAP = 600_000
_spans_lock = locksmith.make_lock("tracectx.spans")
_spans: List[dict] = []       # request spans, most recent
_op_recs: List[Any] = []      # compact op records (tuples) and the dicts of
                              # spans that name an op or a set-up, first come
_op_held = 0                  # spans those entries expand to
_spans_dropped = 0            # spans lost either way


def _push(rec: dict) -> None:
    """A request span into the ring."""
    global _spans_dropped
    with _spans_lock:
        if len(_spans) >= _SPAN_CAP:
            del _spans[:_SPAN_CAP // 4]          # drop the oldest quarter
            _spans_dropped += _SPAN_CAP // 4
        _spans.append(rec)


def _publish(entry: Any, nspans: int = 1) -> None:
    """Keep one op-tree entry worth ``nspans`` spans, or refuse and count it
    once the cap is reached (what came first stays)."""
    global _op_held, _spans_dropped
    with _spans_lock:
        if _op_held + nspans > _OP_SPAN_CAP:
            _spans_dropped += nspans
        else:
            _op_recs.append(entry)
            _op_held += nspans


def dropped() -> int:
    """Spans lost since the last :func:`reset`: op-tree spans refused at
    their cap, request spans pushed out of the ring."""
    return _spans_dropped


def start_span(ctx: Optional[TraceCtx], name: str, who: str,
               **extra: Any) -> Optional[dict]:
    """Open a child span under ``ctx``; returns the record to pass to
    :func:`end_span`, or None when ``ctx`` is absent/unsampled. The record
    is NOT in the buffer until ended — an abandoned record costs nothing."""
    if ctx is None or not ctx.sampled:
        return None
    rec = {"trace": ctx.trace_id, "span": _new_id(), "parent": ctx.span_id,
           "name": name, "who": who, "t0": time.monotonic(), "t1": None,
           "status": "ok"}
    if extra:
        rec.update({k: v for k, v in extra.items() if v is not None})
    return rec


def start_root(name: str, who: str, **extra: Any):
    """Trace birth: one sampling decision, a fresh trace id, and the OPEN
    root span record. Returns ``(ctx, rec)`` — ``ctx.span_id`` is the root
    span itself, so downstream hops parent directly under it — or
    ``(None, None)`` when this request is not sampled."""
    if not sample():
        return None, None
    trace_id = os.urandom(8).hex()
    rec = {"trace": trace_id, "span": _new_id(), "parent": None,
           "name": name, "who": who, "t0": time.monotonic(), "t1": None,
           "status": "ok"}
    if extra:
        rec.update({k: v for k, v in extra.items() if v is not None})
    return TraceCtx(trace_id, rec["span"], True), rec


def end_span(rec: Optional[dict], status: str = "ok", **extra: Any) -> None:
    """Close and publish a span opened by :func:`start_span`."""
    if rec is None:
        return
    rec["t1"] = time.monotonic()
    rec["status"] = status
    if extra:
        rec.update(extra)
    _push(rec)


def emit_span(ctx: Optional[TraceCtx], name: str, who: str, t0: float,
              t1: float, status: str = "ok", **extra: Any) -> Optional[dict]:
    """Publish a span whose bracket was measured elsewhere (a queue wait
    reconstructed at pop time, a pvar op scope's phase spans). Returns the
    published record so callers can parent further children under it."""
    if ctx is None or not ctx.sampled:
        return None
    rec = {"trace": ctx.trace_id, "span": _new_id(), "parent": ctx.span_id,
           "name": name, "who": who, "t0": t0, "t1": t1, "status": status}
    if extra:
        rec.update(extra)
    _push(rec)
    return rec


# ---------------------------------------------------------------------------
# Op span trees of SPMD collectives (no request context): compact records.
# ---------------------------------------------------------------------------

def keep_round(rnd: int) -> bool:
    """The sampling decision of an SPMD collective: a function of the
    channel's round number alone, so every rank keeps the same rounds."""
    rate = sample_rate()
    if rate >= 1.0:
        return True
    if rate <= 0.0:
        return False
    # Knuth's multiplicative hash spreads consecutive rounds over [0, 1)
    return ((rnd * 2654435761) & 0xFFFFFFFF) < rate * 4294967296.0


def op_span_id(cid: Any, rnd: Any, rank: int) -> str:
    """The id of rank ``rank``'s ``op`` span in round ``rnd`` on ``cid``: a
    function of the three, so the watcher names its parent unaided. It is
    the tree's trace id too (one root a trace)."""
    return f"c{cid}r{rnd}k{rank}"


#: a waiter's wait arrives as three phases that tile it; ``rendezvous``,
#: their parent, is drawn from the first to the last
RDV_PARTS = ("rdv_skew", "rdv_fold", "rdv_wake")
#: the phase keys of ``perfvars`` under the names the span tree gives them
_SPAN_NAMES = {"fold": "fold_dispatch", "copy": "copyout"}


def emit_op(coll: str, cid: Any, rnd: Any, rank: int, nbytes: Optional[int],
            lane: str, last: bool, t0: float, t1: float, t_ann: float,
            phases: tuple,
            moved_in: Optional[tuple] = None,
            moved_out: Optional[tuple] = None,
            t_prev: Optional[float] = None) -> None:
    """Publish one op's span tree as ONE compact record. ``t_ann``: by
    then the op's profiler annotation had begun. ``phases`` is the
    op scope's ``(name, t0, t1)`` list; ``moved_in`` / ``moved_out`` =
    (bytes, copies) that ``colocate`` / ``copyout`` moved between chips;
    ``t_prev`` = when the thread's previous op ended (None: it had none), so
    ``t0 - t_prev`` is the caller's own time between the two."""
    _publish(("op", coll, cid, rnd, rank, nbytes, lane, last, t0, t1,
              t_ann, phases, moved_in, moved_out, t_prev), 2 + len(phases))


def emit_round_span(name: str, cid: Any, rnd: Any, rank: int, t0: float,
                    t1: float, **extra: Any) -> None:
    """Publish a span measured off the rank's thread (the watcher's
    ``copy_in.done`` / ``fold.done`` / ``copy_out.done``) as a child of
    rank ``rank``'s op span of that round."""
    op_id = op_span_id(cid, rnd, rank)
    rec = {"trace": op_id, "span": _new_id(), "parent": op_id, "name": name,
           "who": f"rank {rank}", "t0": t0, "t1": t1, "status": "ok",
           "cid": cid, "round": rnd, "rank": rank}
    if extra:
        rec.update(extra)
    _publish(rec)


def emit_setup_span(name: str, t0: float, t1: float, who: str, sid: str,
                    parent: Optional[str] = None, **extra: Any) -> None:
    """Publish a set-up span (``plan.register``, ``fold.compile``,
    ``jitted_fold.compile``, ``kernels.import``, and JAX's own
    ``build.trace`` / ``build.lower`` / ``build.compile`` beneath whichever
    is open) under the id its children already name."""
    rec = {"trace": f"setup:{who}", "span": sid, "parent": parent,
           "name": name, "who": who, "t0": t0, "t1": t1, "status": "ok"}
    if extra:
        rec.update(extra)
    _publish(rec)


def _expand_op(rec: tuple) -> List[dict]:
    (_tag, coll, cid, rnd, rank, nbytes, lane, last, t0, t1, t_ann, phases,
     moved_in, moved_out, t_prev) = rec
    who = f"rank {rank}"
    trace = op_id = op_span_id(cid, rnd, rank)
    out = [{"trace": trace, "span": op_id, "parent": None, "name": "op",
            "who": who, "t0": t0, "t1": t1, "status": "ok", "coll": coll,
            "cid": cid, "round": rnd, "rank": rank, "nbytes": nbytes,
            "lane": lane, "last": last, "t_ann": t_ann}]
    if t_prev is not None:
        out[0]["t_prev"] = t_prev
    def span(sid, parent, name, s0, s1):
        return {"trace": trace, "span": sid, "parent": parent, "name": name,
                "who": who, "t0": s0, "t1": s1, "status": "ok", "cid": cid,
                "round": rnd, "rank": rank}
    rdv = None
    for i, (name, s0, s1) in enumerate(phases):
        name = _SPAN_NAMES.get(name, name)
        parent = op_id
        if name == RDV_PARTS[0]:
            rdv = span(f"{op_id}.{i}r", op_id, "rendezvous", s0, s1)
            out.append(rdv)
        if name in RDV_PARTS and rdv is not None:
            parent, rdv["t1"] = rdv["span"], s1
        elif name == "colocate":        # inside the fold's dispatch
            parent = next((o["span"] for o in out
                           if o["name"] == "fold_dispatch"
                           and o["t0"] <= s0 and s1 <= o["t1"]), op_id)
        sp = span(f"{op_id}.{i}", parent, name, s0, s1)
        if name == "colocate" and moved_in is not None:
            sp["bytes_moved"], sp["copies"] = moved_in
        elif name == "copyout" and moved_out is not None:
            sp["bytes_moved"], sp["copies"] = moved_out
        out.append(sp)
    return out


class span:
    """``with span(ctx, name, who): ...`` — the two calls above as a scope;
    an exception closes the span with error status (and propagates)."""

    __slots__ = ("_rec", "_args", "_kw")

    def __init__(self, ctx: Optional[TraceCtx], name: str, who: str,
                 **extra: Any):
        self._args = (ctx, name, who)
        self._kw = extra

    def __enter__(self) -> Optional[dict]:
        self._rec = start_span(*self._args, **self._kw)
        return self._rec

    def __exit__(self, et, ev, tb) -> bool:
        if et is None:
            end_span(self._rec)
        else:
            end_span(self._rec, status="error", error=type(ev).__name__)
        return False


def child_for_span(rec: Optional[dict],
                   ctx: Optional[TraceCtx]) -> Optional[TraceCtx]:
    """A TraceCtx whose children parent under an OPEN span record — how a
    hop makes its downstream work nest inside its own span."""
    if rec is None or ctx is None:
        return ctx
    return TraceCtx(rec["trace"], rec["span"], True)


def drain(trace_id: Optional[str] = None, t0: Optional[float] = None,
          t1: Optional[float] = None) -> List[dict]:
    """Snapshot (without clearing) the span buffer, optionally filtered to
    one trace, or to the spans that lie inside ``[t0, t1]`` on the
    monotonic clock (an op's tree goes by its ``op`` span, before it is
    expanded). Single-process cpu-sim runs read their whole trace here."""
    with _spans_lock:
        held = _spans + _op_recs
    lo = float("-inf") if t0 is None else t0
    hi = float("inf") if t1 is None else t1
    out: List[dict] = []
    for e in held:
        if isinstance(e, dict):
            if e["t0"] >= lo and (e["t1"] is None or e["t1"] <= hi):
                out.append(e)
        elif e[8] >= lo and e[9] <= hi:
            out.extend(_expand_op(e))
    if trace_id is not None:
        out = [s for s in out if s["trace"] == trace_id]
    return out


def reset() -> None:
    """Clear the buffer (test isolation)."""
    global _op_held, _spans_dropped
    with _spans_lock:
        _spans.clear()
        _op_recs.clear()
        _op_held = 0
        _spans_dropped = 0


def dump_spans(path: str) -> str:
    """Write this process's span buffer as JSON; merge offline with
    :func:`load_spans` over several files."""
    payload = {"version": 1, "pid": os.getpid(),
               "dropped": _spans_dropped, "spans": drain()}
    d = os.path.dirname(os.path.abspath(path))
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(payload, f)
    return path


def load_spans(paths: Any) -> List[dict]:
    """Merge one or more span-dump files back into one span list."""
    if isinstance(paths, str):
        paths = [paths]
    out: List[dict] = []
    for p in paths:
        with open(p) as f:
            payload = json.load(f)
        out.extend(payload.get("spans", ()))
    return out
