"""MPI_T-inspired performance variables (pvars): always-on counters + spans.

The reference ships no tracing at all (SURVEY.md: only ``Wtime``/``Wtick``;
external PMPI/MPI_T tools are assumed) — this module is the layer those
tools would have provided, owned by the runtime itself. Three cooperating
pieces:

- **Per-comm counters** keyed ``(world rank, cid)``: bytes sent/received,
  op counts per ``(collective, algorithm, dtype)``, time blocked in the
  Wait family, host-path phase time (``phase_s``: front_door / lock /
  rendezvous, split into rdv_skew / rdv_fold / rdv_wake / fold / copy),
  bytes and copies moved between chips (``xchip_bytes``,
  ``xchip_copies``) and the rounds folded by the one executable over the
  ranks' chips, which moves its bytes itself (``ingraph_folds``), chunk-pipeline overlap inputs, RMA epoch counts, and
  per-collective latency histograms (log2-µs buckets,
  ``config.pvars_hist_bins`` wide). Plan-cache hits/misses ride along at
  snapshot time from ``overlap.plans.stats()``, the wall time spent
  registering plans and compiling folds as ``arming_s``, the attention
  calls built into traced programs as the fused kernel or the plain path
  as ``attn_lowerings``, the experts' grouped multiplications as the
  grouped kernel or `lax.ragged_dot` as ``gmm_lowerings``, and the sums of
  rows into indexed places as the product on the MXU or XLA's scatter-add
  as ``row_sum_lowerings``, the rotary embeddings by their form as
  ``rope_forms``, the layers' mixers by kind as ``mixer_kinds`` and the
  state-space scans by their form as ``scan_lowerings`` and by who computes
  them (the Pallas kernels or plain `jnp`) as ``scan_kernel_lowerings``, the
  per-channel selective scans likewise as ``sel_scan_lowerings`` and
  ``sel_scan_kernel_lowerings``, the delta-rule scans as ``delta_lowerings``,
  ``delta_kernel_lowerings`` and (a decay a head or a key channel)
  ``delta_decays``, the layers
  that read a value beside the residual stream as ``side_values``, the
  vocabulary heads and their losses as ``head_loss_lowerings`` (over blocks
  of tokens, or over the whole logits) with ``head_loss_blocks``, and
  what JAX
  traced, lowered, compiled and read
  from its persistent cache, by function, with the Pallas kernels built
  under those traces, as the family ``build`` (:func:`listen_builds`;
  docs/observability.md "Set-up spans").
  ``fold`` and ``copy`` on device operands are DISPATCH times: the host
  seconds it took to enqueue the fold (its operand copies included) and
  the copy-out, not the seconds the device worked. The device's end of
  them is the watcher's (below) or a ``jax.profiler`` trace's.
- **The op span tree** (docs/observability.md "Op spans"): with
  ``trace_sample > 0`` the op scope opened here is published whole, one
  tree per op, into ``tracectx``'s buffer: under a serve-tier request
  context as children of the request span, and on a plain SPMD rank thread
  under a root of its own keyed ``(cid, round, rank)``; the round decides,
  alike on every rank, whether the op is sampled (the channel's door,
  :func:`enter_channel`). From there to its end a sampled op also holds one
  ``jax.profiler.TraceAnnotation("tpu_mpi:<coll>")`` carrying ``mono_ns``,
  so a device profile and the spans share a clock, and one watcher thread
  stamps when a registered fold's output was ready on the device
  (``fold.done``, on one chip as across chips) and, where bytes crossed
  chips, when the copies were (``copy_in.done``, ``copy_out.done``). The
  ``op`` span carries ``t_prev``, when the thread's previous op ended: the
  caller's own time between two ops.
- **Timed spans** on the event IR: when tracing is on, the op scope opened
  here stamps the recorded :class:`~tpu_mpi.analyze.events.Event` with
  ``t_start``/``t_end`` and the phase spans the channels observed, which
  :mod:`tpu_mpi.analyze.timeline` renders as a Chrome-trace / Perfetto
  timeline.
- **Runtime control**: the MPI-standard ``Pcontrol(level)``
  (:func:`tpu_mpi.environment.Pcontrol` delegates here) — 0 disables, 1
  enables (the default), >= 2 enables AND flushes a dump immediately.

Overhead discipline (the ``analyze.events.enabled()`` contract): every hot
hook front-loads :func:`enabled` — one tuple compare against
``config.GENERATION`` — so a ``TPU_MPI_PVARS=0`` run pays a single
predictable branch per operation. What the default ``pvars = 1`` costs is
read on the chip, not on a CPU: the benchmark's untraced runs pay it
(``coll_latency_p50`` of ``osu-allreduce-4r1c.small-reuse`` in
``PERF_LEDGER.jsonl``), and PERF.md sections 5 and 6 give the traced run's
reading beside the untraced one, which is what the span tree adds.

Span-attribution caveat: phase spans collect into a thread-local op scope,
so a BLOCKING collective that routes through the nonblocking worker (only
when that comm has outstanding ``I*`` ops) keeps its counters but loses its
per-phase spans — the worker thread owns no scope for it (PERF.md
section 7 lists it among what the measurement cannot see).
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

from . import config
from . import tracectx as _tc
from typing import Any, Dict, List, Optional, Tuple

monotonic = time.monotonic

PHASES = ("rendezvous", "fold", "copy",
          # hierarchical-composite sub-phases (backend._run_hier_*)
          "intra_fold", "inter_exchange", "allgather",
          # the thread tier's call, from its entry to the device
          # (_runtime.CollectiveChannel): the front door up to the channel,
          # the channel's condvar, and a waiter's rendezvous cut in three
          "front_door", "lock", "rdv_skew", "rdv_fold", "rdv_wake")

_UNSET = object()
_enabled_cache: Tuple[Any, bool] = (_UNSET, False)
# Pcontrol's runtime override: None = follow config.pvars.
_level_override: Optional[int] = None
_store_lock = threading.Lock()
_store: Dict[Tuple[int, int], "CommPvars"] = {}
# bumped whenever accumulators are dropped from _store, so the per-thread
# _acct caches never keep writing into an orphaned accumulator
_store_gen = 0


class _TLS(threading.local):
    # class-attribute defaults: fresh threads read these without the
    # AttributeError/getattr-default dance on the hot path
    scope = None                      # the open _OpScope of this thread
    t_prev = None                     # when this thread's last op ended
    setup = None                      # id of the open setup_span, if any
    tracing = 0                       # JAX traces open on this thread
    cache_read = None                 # the open compile's "hit" | "miss"
    acct = None                       # (store_gen, {key: CommPvars}) cache
    wait_owned = False                # a wait-time owner is on the stack


_tls = _TLS()


def _config_level() -> int:
    if _level_override is not None:
        config.load()               # keep GENERATION meaningful for the gate
        return _level_override
    return int(config.load().pvars)


def enabled() -> bool:
    """Whether pvar collection is on — cached on ``config.GENERATION`` so
    the per-operation cost of a disabled run is one tuple compare."""
    global _enabled_cache
    cached_gen, val = _enabled_cache
    if cached_gen == config.GENERATION:
        return val
    val = _config_level() >= 1
    _enabled_cache = (config.GENERATION, val)
    return val


def level() -> int:
    """The effective collection level (0 off, 1 on; >= 2 behaves as 1 —
    the flush side effect belongs to :func:`pcontrol` itself)."""
    return _config_level()


def pcontrol(lvl: int) -> int:
    """Runtime toggle (the ``MPI_Pcontrol`` contract): 0 disables
    collection, 1 restores the default (the ``pvars`` config knob), and
    any level >= 2 enables collection and immediately flushes a dump to
    ``config.pvars_dump`` (when set). Returns the effective level."""
    global _level_override, _enabled_cache
    lvl = int(lvl)
    if lvl < 0:
        lvl = 0
    _level_override = None if lvl == 1 else lvl
    _enabled_cache = (config.GENERATION, _config_level() >= 1)
    if lvl >= 2:
        finalize_dump(force=True)
    return _config_level()


class CommPvars:
    """The counter set of one ``(world rank, cid)`` pair."""

    __slots__ = ("rank", "cid", "size", "bytes_sent", "bytes_recv", "sends",
                 "recvs", "wait_ns", "ops", "times", "phase_ns", "rma",
                 "hist", "pipe_ops", "pipe_chunks", "pipe_fold_ns",
                 "pipe_wait_ns", "explore_calls", "explore_explored",
                 "table_swaps", "last_swap_gen", "batch_flushes",
                 "batch_ops", "xchip_bytes", "xchip_copies", "ingraph_folds")

    def __init__(self, rank: int, cid: int):
        self.rank = rank
        self.cid = cid
        self.size = 0
        self.bytes_sent = 0
        self.bytes_recv = 0
        self.sends = 0
        self.recvs = 0
        self.wait_ns = 0
        # (coll, algo, dtype) -> op count
        self.ops: Dict[Tuple[str, str, str], int] = {}
        # (coll, algo, nbytes) -> [count, total_ns, min_ns, max_ns]
        self.times: Dict[Tuple[str, str, int], List[int]] = {}
        self.phase_ns = {p: 0 for p in PHASES}
        self.rma = {"fence": 0, "lock": 0, "flush": 0}
        self.hist: Dict[str, List[int]] = {}      # coll -> log2-µs buckets
        # chunk-pipeline overlap inputs (see snapshot() for the derived
        # fraction): fold time + post-first-chunk rendezvous waits of
        # pipelined star roots
        self.pipe_ops = 0
        self.pipe_chunks = 0
        self.pipe_fold_ns = 0
        self.pipe_wait_ns = 0
        # online bandit autotuner (tpu_mpi.tune_online): decisions seen,
        # decisions routed to an alternate arm, hot-swaps performed on this
        # comm, and the config generation of the last swap.
        self.explore_calls = 0
        self.explore_explored = 0
        self.table_swaps = 0
        self.last_swap_gen = 0
        # batched rendezvous submission (ISSUE-11): flushes and the ops
        # they carried — occupancy = ops / flushes
        self.batch_flushes = 0
        self.batch_ops = 0
        # bytes whose source and destination devices differ, and the copies
        # this rank enqueued to move them (operands to the folding chip,
        # results back); the rounds this rank, as last arriver, folded with
        # the executable over the ranks' chips instead: their bytes cross
        # inside it, by no copy
        self.xchip_bytes = 0
        self.xchip_copies = 0
        self.ingraph_folds = 0

    def snapshot(self) -> dict:
        bins = max(4, int(config.load().pvars_hist_bins))
        pipe_busy = self.pipe_fold_ns + self.pipe_wait_ns
        return {
            "rank": self.rank, "cid": self.cid, "size": self.size,
            "bytes_sent": self.bytes_sent, "bytes_recv": self.bytes_recv,
            "sends": self.sends, "recvs": self.recvs,
            "wait_s": self.wait_ns / 1e9,
            "xchip_bytes": self.xchip_bytes,
            "xchip_copies": self.xchip_copies,
            "ingraph_folds": self.ingraph_folds,
            "ops": {"|".join(k): v for k, v in sorted(self.ops.items())},
            "times": [{"coll": c, "algo": a, "nbytes": b, "count": t[0],
                       "total_s": t[1] / 1e9, "min_s": t[2] / 1e9,
                       "max_s": t[3] / 1e9}
                      for (c, a, b), t in sorted(self.times.items())],
            "phase_s": {p: ns / 1e9 for p, ns in self.phase_ns.items()},
            "rma": dict(self.rma),
            "hist_bins": bins,
            "hist": {c: list(h) for c, h in sorted(self.hist.items())},
            "pipeline": {
                "ops": self.pipe_ops, "chunks": self.pipe_chunks,
                "fold_s": self.pipe_fold_ns / 1e9,
                "wait_after_first_s": self.pipe_wait_ns / 1e9,
                # 1.0 = every post-first-chunk contribution had already
                # landed when the root finished the previous fold (transfer
                # fully hidden behind compute); 0.0 = fully serial
                "overlap_fraction": (round(self.pipe_fold_ns / pipe_busy, 4)
                                     if pipe_busy else None),
            },
            "explore": {
                "calls": self.explore_calls,
                "explored": self.explore_explored,
                "fraction": (round(self.explore_explored
                                   / self.explore_calls, 4)
                             if self.explore_calls else None),
                "table_swaps": self.table_swaps,
                "last_swap_gen": self.last_swap_gen,
            },
            "batch": {
                "flushes": self.batch_flushes,
                "ops": self.batch_ops,
                "occupancy": (round(self.batch_ops / self.batch_flushes, 4)
                              if self.batch_flushes else None),
            },
        }


def _acct(comm: Any = None, cid: Optional[int] = None,
          size: int = 0) -> Optional[CommPvars]:
    """The accumulator of (current world rank, comm's cid), creating it on
    first touch; None outside an SPMD environment."""
    from ._runtime import current_env
    env = current_env()
    if env is None:
        return None
    rank = env[1]
    if comm is not None:
        cid = comm.cid
    elif cid is None:
        cid = -1                      # unattributed (no comm at the hook)
    key = (rank, cid)
    cached = _tls.acct
    if cached is not None and cached[0] == _store_gen:
        acct = cached[1].get(key)
        if acct is not None:
            if comm is not None and not acct.size:
                acct.size = size or len(comm.group)
            return acct
    with _store_lock:
        acct = _store.get(key)
        if acct is None:
            acct = _store[key] = CommPvars(rank, cid)
        if comm is not None and not acct.size:
            acct.size = size or len(comm.group)
    if cached is None or cached[0] != _store_gen:
        cached = _tls.acct = (_store_gen, {})
    cached[1][key] = acct
    return acct


# ---------------------------------------------------------------------------
# Op scope: per-op span collection shared with the event IR
# ---------------------------------------------------------------------------

class _OpScope:
    """One op's scope. Class-level defaults, so opening one stores two
    attributes; what only a sampled span tree needs is set by whoever
    needs it."""

    ev: Any = None            # the trace Event of this op, if any
    trace: Any = None         # the request TraceCtx, when sampled
    nested: Any = None        # spans inside a phase (``colocate``)
    tree = False              # publish this op's own span tree
    ann: Any = None           # the live profiler annotation, if any
    t_ann = 0.0               # ... had begun by then
    cid: Any = None
    round: Any = None         # the channel's round (first, if many)
    rank = -1                 # rank on the op's communicator
    last = False              # this rank was the round's last arriver
    lane = "legacy"           # "armed": the registered round ran it
    meta: Any = None          # (coll, algo, dtype, nbytes) for op_end
    moved_in: Any = None      # [bytes, copies] colocate moved ...
    moved_out: Any = None     # ... and copy-out, between chips
    exchanged: Any = None     # [bytes, rounds] the fold over chips moved

    def __init__(self):
        self.t0 = monotonic()
        # phases of this op, disjoint: (name, t0, t1)
        self.spans: List[Tuple[str, float, float]] = []


def scope() -> Optional[_OpScope]:
    """The open op scope of this thread (channels append phase spans to
    ``scope().spans``), or None."""
    return _tls.scope


def op_begin() -> Optional[_OpScope]:
    """Open an op scope on this thread. Returns None when one is already
    open — the outermost owner finalizes (``Allreduce`` opens it on entry,
    so the front door, the rendezvous and the copy-out land in one phase
    breakdown; whoever runs under it fetches it with :func:`scope`)."""
    if _tls.scope is not None:
        return None
    sc = _OpScope()
    if _tc.enabled():
        # span sampling is on: attach the op to the request trace context
        # bound to this thread, so the op's phase spans become children of
        # the request span (one tuple compare when sampling is off)
        sc.trace = _tc.current()
    _tls.scope = sc
    return sc


def enter_channel(sc: _OpScope, cid: Any, rnd: int, rank: int,
                  opname: str) -> None:
    """The op in ``sc`` is at its channel's door, about to run round
    ``rnd`` of communicator ``cid`` as ``rank``. On an SPMD rank thread (no
    request context) that round decides, alike on every rank, whether the
    op publishes its span tree; if so it holds one profiler annotation from
    here to its end, which carries the round and the monotonic clock."""
    if sc.trace is not None or not _tc.keep_round(rnd):
        return
    sc.tree, sc.cid, sc.round, sc.rank = True, cid, rnd, rank
    import jax
    # one clock with a device profile: (profiler time, monotonic time)
    # pairs, from the annotation's start and the stat it carries. The
    # annotation begins between two clock reads, ``mono_ns`` and ``t_ann``:
    # what a reader holds the aligned clocks to
    sc.ann = jax.profiler.TraceAnnotation(
        "tpu_mpi:" + opname.split("@", 1)[0].lower(), cid=str(cid),
        round=rnd, rank=rank, mono_ns=int(monotonic() * 1e9))
    sc.ann.__enter__()
    sc.t_ann = monotonic()


def op_end(sc: _OpScope, comm: Any = None, coll: Optional[str] = None,
           algo: Optional[str] = None, dtype: Optional[str] = None,
           nbytes: Optional[int] = None) -> None:
    """Close the scope: stamp the op's trace event (t_start/t_end/phases)
    and fold duration + spans into the per-comm counters. Without ``coll``
    the op is described by ``sc.meta``, which the code that ran it left."""
    _tls.scope = None
    if coll is None and sc.meta is not None:
        coll, algo, dtype, nbytes = sc.meta
    shim = _shim_map()
    if shim and coll is not None:
        # test/debug latency shim (config.tune_shim): the sleep lands
        # BEFORE t1 so it is part of the measured span and is attributed
        # to this (coll, algo) arm — the knob the bandit-convergence tests
        # use to make one arm deterministically lose.
        pause = shim.get((coll, algo or "star"))
        if pause:
            time.sleep(pause)
    t1 = monotonic()
    if sc.ann is not None:
        sc.ann.__exit__(None, None, None)
    ev = sc.ev
    if ev is not None:
        ev.t_start = sc.t0
        ev.t_end = t1
        if sc.spans:
            # the event IR keeps a waiter's wait whole, as ``rendezvous``
            ev.phases = [("rendezvous", s0, sc.spans[i + 2][2])
                         if name == _tc.RDV_PARTS[0] else (name, s0, s1)
                         for i, (name, s0, s1) in enumerate(sc.spans)
                         if name not in _tc.RDV_PARTS[1:]]
    if sc.tree:
        # with ``t_prev``, when this thread's last op ended: what the caller
        # did between the two is on the tree
        _tc.emit_op(coll or "op", sc.cid, sc.round, sc.rank, nbytes, sc.lane,
                    sc.last, sc.t0, t1, sc.t_ann,
                    tuple(sc.spans + sc.nested if sc.nested else sc.spans),
                    tuple(sc.moved_in) if sc.moved_in else None,
                    tuple(sc.moved_out) if sc.moved_out else None,
                    _tls.t_prev)
    elif sc.trace is not None:
        # per-rank request span: the op bracket parents under the request
        # context, and each measured phase nests under the op span
        from ._runtime import current_env
        env = current_env()
        who = f"rank {env[1]}" if env is not None else "rank ?"
        rec = _tc.emit_span(sc.trace, coll or "op", who, sc.t0, t1,
                            algo=algo, nbytes=nbytes)
        if rec is not None and sc.spans:
            pctx = _tc.TraceCtx(rec["trace"], rec["span"], True)
            for name, s0, s1 in sc.spans:
                _tc.emit_span(pctx, name, who, s0, s1)
    _tls.t_prev = t1
    if not enabled() or coll is None:
        return
    acct = _acct(comm)
    if acct is None:
        return
    bins = max(4, int(config.load().pvars_hist_bins))
    dur_ns = int((t1 - sc.t0) * 1e9)
    key = (coll, algo or "star", -1 if nbytes is None else int(nbytes))
    phase_ns = acct.phase_ns
    okey = (coll, algo or "star", dtype or "?")
    idx = (dur_ns // 1000).bit_length()   # log2 bucket of the µs latency
    with _store_lock:
        acct.ops[okey] = acct.ops.get(okey, 0) + 1
        t = acct.times.get(key)
        if t is None:
            acct.times[key] = [1, dur_ns, dur_ns, dur_ns]
        else:
            t[0] += 1
            t[1] += dur_ns
            if dur_ns < t[2]:
                t[2] = dur_ns
            if dur_ns > t[3]:
                t[3] = dur_ns
        for name, s0, s1 in sc.spans:
            if name in phase_ns:
                ns = int((s1 - s0) * 1e9)
                phase_ns[name] += ns
                if name in _tc.RDV_PARTS:    # they tile one rendezvous wait
                    phase_ns["rendezvous"] += ns
        for moved in (sc.moved_in, sc.moved_out):
            if moved is not None:
                acct.xchip_bytes += moved[0]
                acct.xchip_copies += moved[1]
        if sc.exchanged is not None:
            acct.xchip_bytes += sc.exchanged[0]
            acct.ingraph_folds += sc.exchanged[1]
        hist = acct.hist.get(coll)
        if hist is None:
            hist = acct.hist[coll] = [0] * bins
        hist[min(idx, len(hist) - 1)] += 1


def note_moved(sc: _OpScope, inward: bool, nbytes: int) -> None:
    """One copy between chips that this op enqueued: an operand to the
    folding chip (``inward``) or the result to the rank's own."""
    moved = sc.moved_in if inward else sc.moved_out
    if moved is None:
        moved = [0, 0]
        if inward:
            sc.moved_in = moved
        else:
            sc.moved_out = moved
    moved[0] += int(nbytes)
    moved[1] += 1


def note_exchanged(sc: _OpScope, nbytes: int) -> None:
    """A round of this op (a batched flush holds several) was folded by
    the one executable over the ranks' chips: ``nbytes`` crossed chips
    inside it, and no copy was enqueued."""
    if sc.exchanged is None:
        sc.exchanged = [0, 0]
    sc.exchanged[0] += int(nbytes)
    sc.exchanged[1] += 1


# -- arming: plan registration and fold compiles ----------------------------
#
# Rare (once per signature and rank), so they are timed whenever pvars are
# on: the wall time under them is the snapshot's ``arming_s`` (the union of
# the top-level brackets over all threads: four ranks register at once),
# and with span sampling on each is a span in tracectx's buffer.

_arming: List[Tuple[float, float]] = []
_ARMING_CAP = 4096


def publish_setup_span(name: str, t0: float, t1: float,
                       sid: Optional[str] = None, **attrs: Any) -> None:
    """With span sampling on, one span of the ``setup:`` trace from this
    thread, child of the :class:`setup_span` open on it: a span alone, in no
    pvar (JAX's ``build.*``, the Pallas import's ``kernels.import``)."""
    if _tc.enabled():
        from ._runtime import current_env
        env = current_env()
        who = f"rank {env[1]}" if env is not None else "rank ?"
        _tc.emit_setup_span(name, t0, t1, who, sid or _tc.new_id(),
                            _tls.setup, **attrs)


class setup_span:
    """``with setup_span("plan.register", cid=...)``: time one piece of
    arming. Nested ones (``fold.compile`` under ``plan.register``) are its
    children and add nothing to ``arming_s``."""

    __slots__ = ("name", "attrs", "on", "t0", "sid", "parent")

    def __init__(self, name: str, **attrs: Any):
        self.name, self.attrs = name, attrs

    def __enter__(self) -> "setup_span":
        self.on = enabled()
        if self.on:
            self.parent = _tls.setup
            self.sid = _tls.setup = _tc.new_id()
            self.t0 = monotonic()
        return self

    def __exit__(self, *exc) -> bool:
        if not self.on:
            return False
        t1 = monotonic()
        _tls.setup = self.parent
        if self.parent is None:
            with _store_lock:
                if len(_arming) < _ARMING_CAP:
                    _arming.append((self.t0, t1))
        publish_setup_span(self.name, self.t0, t1, self.sid, **self.attrs)
        return False


def arming_seconds() -> float:
    """Wall seconds under a top-level set-up span so far, on any thread."""
    with _store_lock:
        spans = sorted(_arming)
    total, end = 0.0, float("-inf")
    for s0, s1 in spans:
        if s1 > end:
            total += s1 - max(s0, end)
            end = s1
    return total


# -- what the traced programs hold ---------------------------------------------
#
# While a program is traced the model chooses: a kernel or its plain path
# (`xla/choice.py`, the one rule), a form, a layer's mixer. Each choice is
# one :func:`note` where it is made, so a run can say what its compiled
# steps hold. The families are data: a line here is all this module knows
# of a mechanism. A tuple: the kinds a snapshot shows at 0 before anything
# is traced (a kind outside it appears once it is traced). A function: the
# family is noted under keys of its own and shown as the function makes it.

def _how_by_kind(counts: dict) -> dict:
    """{kind of attention: "fused" | "plain" | "mixed"} from the counts of
    (kind, how) pairs."""
    hows: Dict[str, set] = {}
    for kind, how in counts:
        hows.setdefault(kind, set()).add(how)
    return {kind: how.pop() if len(how) == 1 else "mixed"
            for kind, how in sorted(hows.items())}


FAMILIES: Dict[str, Any] = {
    # `parallel.ring.local_attention`: the fused kernel or the einsum path
    "attn_lowerings": ("fused", "plain"),
    # the same calls as (kind, how): "full" | "window" | "latent", and
    # "diff" beside them (`models.transformer._diff_attn`)
    "attn_kinds": _how_by_kind,
    # `parallel.ep.grouped_products`, one count a product
    "gmm_lowerings": ("kernel", "ragged_dot"),
    # `parallel.ep.sum_rows` / `rows_at`: the product on the MXU or XLA's
    # scatter-add
    "row_sum_lowerings": ("product", "scatter"),
    # `models.transformer._rope` and the two rotation kernels: x cos +
    # swap(x) sin with its own backward, or two half-width products
    "rope_forms": ("dense", "halves"),
    # `_attn_ffn_block`, one count a trace of a layer kind; `mamba`, `gmu`,
    # `cross`, `gdn` and `kda` appear once traced
    "mixer_kinds": ("attention", "ssm"),
    # `_side_read`: the layers that read a value written beside the stream
    "side_values": ("memory", "kv"),
    # `parallel.ssm.scan`: its form (`padded`: filled up to whole chunks)
    "scan_lowerings": ("chunked", "padded"),
    # and who computes it: `xla/ssm_kernels.py` or `parallel.ssm._chunked`
    "scan_kernel_lowerings": ("kernel", "plain"),
    # `parallel.ssm.selective_scan` likewise: its form
    "sel_scan_lowerings": ("chunked", "padded"),
    # and who: `xla/sel_scan_kernels.py` or `_selective_chunks`
    "sel_scan_kernel_lowerings": ("kernel", "plain"),
    # `parallel.delta.delta_scan` likewise: its form
    "delta_lowerings": ("chunked", "padded"),
    # and who: `xla/delta_kernels.py` or `parallel.delta._chunked`
    "delta_kernel_lowerings": ("kernel", "plain"),
    # and by its decay: one number a head and token, or one a key channel
    "delta_decays": ("head", "channel"),
    # `parallel.ssm.conv_silu`, a recurrent mixer's convolution and silu:
    # `xla/conv_kernels.py` or `causal_conv` and `jax.nn.silu`
    "conv_kernel_lowerings": ("kernel", "plain"),
    # `models.transformer._l2_normed` and `_head_norm_gated`, a delta-rule
    # mixer's per-head norms over rows: `xla/head_norm_kernels.py` or XLA's
    # passes over [batch, t, heads, width]
    "head_norm_lowerings": ("kernel", "plain"),
    # `models.transformer.head_loss` over blocks of tokens, or `_xent` of
    # the whole logits (the two pipelined steps), one count a traced loss
    "head_loss_lowerings": ("blocked", "whole"),
    # the traced `blocked` losses by their number of blocks
    "head_loss_blocks": lambda counts: {str(n): c
                                        for n, c in sorted(counts.items())},
}


def _no_counts() -> Dict[str, dict]:
    return {family: dict.fromkeys(kinds, 0) if isinstance(kinds, tuple)
            else {} for family, kinds in FAMILIES.items()}


_counts = _no_counts()


def note(family: str, kind: Any, n: int = 1) -> None:
    """``n`` more of ``kind`` were built into a traced program, in
    ``family`` of :data:`FAMILIES`."""
    with _store_lock:
        counts = _counts[family]
        counts[kind] = counts.get(kind, 0) + n


# -- build: what JAX traced, lowered, compiled and read from its cache --------
#
# JAX times these boundaries itself, inside its own context managers, and
# hands each time to whoever listens (``jax.monitoring``), with the
# function's name and with no frame of ours on the traced call stack: a
# Mosaic kernel's serialized body carries that stack and is part of the
# persistent cache's key, so a timing wrapper around a lowering misses the
# cache that a plain run hits. :func:`listen_builds` is the one listener;
# the family ``build`` of :func:`snapshot` is what it keeps.

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
#: the phases of a build, and where each one's [n, s] pair starts in a row
_BUILD_PHASES = {_TRACE_EVENT: 0,
                 "/jax/core/compile/jaxpr_to_mlir_module_duration": 2,
                 "/jax/core/compile/backend_compile_duration": 4}
_BUILD_NAMES = ("trace", "lower", "compile")
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                 "/jax/compilation_cache/cache_misses": "misses"}
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_SECONDS = {"/jax/compilation_cache/cache_retrieval_time_sec": "load_s",
                  "/jax/compilation_cache/compile_time_saved_sec": "saved_s"}
_BUILD_FUN_CAP = 256                    # names kept in ``by_fun`` ...
BUILD_REST = "(others)"                 # ... and the key the rest sum under

_build_listening = False
_wall_to_mono = 0.0                     # JAX stamps time.time(); spans don't
_build_total = [0, 0.0, 0, 0.0, 0, 0.0]         # [n, s] x trace, lower, compile
_build_cache = {"hits": 0, "misses": 0, "load_s": 0.0, "saved_s": 0.0}
_build_by_fun: Dict[str, List[float]] = {}      # name -> a row like the total
_build_step: set = set()
_build_kernels: Dict[str, int] = {}


def _build_fun(name: Any) -> str:
    """One key for the three phases of one program: JAX names the trace by
    the function (``local_step``) and the lowered module and the compile by
    the module (``jit(local_step)``; ``jit_local_step`` in older ones)."""
    name = str(name)
    if name.startswith("jit(") and name.endswith(")"):
        return name[4:-1]
    return name[4:] if name.startswith("jit_") else name


def _on_build_enter(event: str, _start: float, **_kw: Any) -> None:
    """JAX's scalar at a timed block's entry: traces nest (a jitted function
    called under a trace is traced inside it), and only the outermost one's
    seconds may go into the total."""
    if event == _TRACE_EVENT:
        _tls.tracing += 1


def _on_build_span(event: str, start: float, end: float, **kw: Any) -> None:
    """A trace, a lowering or a backend compile has ended on this thread,
    from ``start`` to ``end`` on JAX's ``time.time()``."""
    at = _BUILD_PHASES.get(event)
    if at is None:
        return
    outermost, cache = True, None
    if at == 0:
        _tls.tracing = depth = max(0, _tls.tracing - 1)
        outermost = depth == 0
    elif at == 4:
        cache, _tls.cache_read = _tls.cache_read or "off", None
    if not enabled():
        return
    fun, secs = _build_fun(kw.get("fun_name", "?")), end - start
    with _store_lock:
        row = _build_by_fun.get(fun)
        if row is None:
            if len(_build_by_fun) >= _BUILD_FUN_CAP:
                fun = BUILD_REST
            row = _build_by_fun.setdefault(fun, [0, 0.0, 0, 0.0, 0, 0.0])
        row[at] += 1
        row[at + 1] += secs
        if outermost:
            _build_total[at] += 1
            _build_total[at + 1] += secs
    if _tc.enabled():
        attrs = {"fun": fun}
        if at == 4:
            attrs["cache"] = cache
        publish_setup_span("build." + _BUILD_NAMES[at // 2],
                           start + _wall_to_mono, end + _wall_to_mono, **attrs)


def _on_cache_event(event: str, **_kw: Any) -> None:
    """The persistent cache was asked for the executable that this thread
    is compiling, had it (a hit), or was given it to keep (a miss)."""
    key = _CACHE_EVENTS.get(event)
    if key == "hits":
        _tls.cache_read = "hit"
    elif event == _CACHE_ASKED:
        _tls.cache_read = "miss"
    if key is not None and enabled():
        with _store_lock:
            _build_cache[key] += 1


def _on_cache_seconds(event: str, secs: float, **_kw: Any) -> None:
    key = _CACHE_SECONDS.get(event)
    if key is not None and enabled():
        with _store_lock:
            _build_cache[key] += secs


def listen_builds() -> bool:
    """Register, once, the listener behind the family ``build``. Called
    where the program first touches JAX (the step builders of
    ``models/transformer.py``, the thread tier's start, ``Init``); a process
    that has not imported jax is left without it, and so is one with pvars
    off. Returns whether the listener is there."""
    global _build_listening, _wall_to_mono
    if _build_listening:
        return True
    if "jax" not in sys.modules or not enabled():
        return False
    from jax import monitoring
    with _store_lock:
        if _build_listening:
            return True
        _build_listening = True
        # one offset for the run: JAX's stamps are wall-clock readings
        _wall_to_mono = monotonic() - time.time()
    monitoring.register_scalar_listener(_on_build_enter)
    monitoring.register_event_time_span_listener(_on_build_span)
    monitoring.register_event_listener(_on_cache_event)
    monitoring.register_event_duration_secs_listener(_on_cache_seconds)
    return True


def note_step_fun(name: str) -> None:
    """A step builder names the function it is about to jit: ``build.step``
    is how a reader finds the step's rows in ``by_fun``. A step builder is
    also where such a program first touches JAX, so the listener is
    registered here."""
    listen_builds()
    if enabled():
        with _store_lock:
            _build_step.add(name)


def note_kernel_build(name: str) -> None:
    """One ``pallas_call`` was built under a trace: its body traced now, and
    lowered by Mosaic when the program around it is."""
    if enabled():
        with _store_lock:
            _build_kernels[name] = _build_kernels.get(name, 0) + 1


def _build_pairs(row: List[float]) -> dict:
    return {name: {"n": row[2 * i], "s": row[2 * i + 1]}
            for i, name in enumerate(_BUILD_NAMES)}


def build_snapshot() -> dict:
    """The ``build`` family of :func:`snapshot`, empty until something was
    built or noted:

    - ``trace`` / ``lower`` / ``compile``: ``{"n", "s"}``, events and
      seconds of JAX's ``jaxpr_trace_duration``,
      ``jaxpr_to_mlir_module_duration`` and ``backend_compile_duration``. A
      trace inside another counts under its own name in ``by_fun`` and not
      again here. ``compile`` is the backend's compile OR the persistent
      cache's read in its place: a hit's retrieval lies inside it.
    - ``cache``: ``hits`` and ``misses`` (executables read from the
      persistent cache, and compiled and written to it), ``load_s`` the
      seconds the reads took, ``saved_s`` the compile seconds they stood for.
    - ``by_fun``: the three pairs by function (``jit(f)`` and ``f`` meet
      under ``f``), a nested trace's seconds in its callers' too; the first
      256 names, the rest summed under :data:`BUILD_REST`.
    - ``step``: the names the step builders gave their jitted functions.
    - ``kernels``: ``pallas_call``s built under a trace, by their name."""
    with _store_lock:
        if not (_build_by_fun or _build_step or _build_kernels
                or any(_build_cache.values())):
            return {}
        return {**_build_pairs(_build_total), "cache": dict(_build_cache),
                "by_fun": {f: _build_pairs(r)
                           for f, r in sorted(_build_by_fun.items())},
                "step": sorted(_build_step),
                "kernels": dict(sorted(_build_kernels.items()))}


# -- the device's end of a fold and of a copy between chips ------------------
#
# A host span around an asynchronous launch or copy times the enqueue. An op
# that publishes its span tree hands what it enqueued (a registered fold's
# output on any lane; across chips the operands and the results too) to ONE
# watcher thread, which waits for the arrays in the order they came
# (``block_until_ready`` releases the GIL) and publishes when they were
# done. The rank threads never wait for it. It holds the arrays alive
# meanwhile, and no longer, which is what ``trace_sample`` bounds: watching
# EVERY round of the large-message star cost the four-chip cell 29% of its
# bandwidth (the folding chip's memory fills a round sooner; 16.2 against
# 22.8 GB/s), one round in 8 nothing that can be read (PERF.md section 6,
# PR 23).

_watch_q: Any = None


def _watch_loop(q: Any) -> None:
    import jax
    while True:
        cid, rnd, rank, t0, stages = q.get()
        try:
            for name, arrays in stages:
                jax.block_until_ready(arrays)
                _tc.emit_round_span(name, cid, rnd, rank, t0, monotonic())
        except RuntimeError:    # the array was donated or deleted meanwhile:
            pass                # this round's remaining stages go unstamped
        stages = arrays = None  # nothing is held while the queue is empty


def watch(sc: _OpScope, t0: float, *stages: Tuple[str, Any]) -> None:
    """Hand ``(span name, arrays)`` stages of the op in ``sc`` (one that
    publishes its tree) to the watcher: each span runs from ``t0`` (the
    dispatch) to the moment its arrays, and those of the stages before it,
    were ready."""
    global _watch_q
    q = _watch_q
    if q is None:
        with _store_lock:
            q = _watch_q
            if q is None:
                import queue
                q = _watch_q = queue.SimpleQueue()
                threading.Thread(target=_watch_loop, args=(q,), daemon=True,
                                 name="tpu_mpi-span-watcher").start()
    q.put((sc.cid, sc.round, sc.rank, t0, stages))


# -- test/debug latency shim (config.tune_shim) ------------------------------

_shim_cache: Tuple[Any, Optional[Dict[Tuple[str, str], float]]] = (_UNSET, None)


def _shim_map() -> Optional[Dict[Tuple[str, str], float]]:
    """Parsed ``tune_shim`` spec ("coll:algo=microseconds,...") as
    {(coll, algo): seconds}, or None when unset. Generation-cached: the
    default (empty) spec costs one tuple compare per op."""
    global _shim_cache
    cached_gen, val = _shim_cache
    if cached_gen == config.GENERATION:
        return val
    spec = config.load().tune_shim
    out: Optional[Dict[Tuple[str, str], float]] = None
    if spec:
        out = {}
        for part in spec.split(","):
            part = part.strip()
            if not part:
                continue
            key, _, us = part.partition("=")
            coll, _, algo = key.partition(":")
            try:
                out[(coll.strip(), (algo or "star").strip())] = \
                    float(us) / 1e6
            except ValueError:
                pass
        out = out or None
    _shim_cache = (config.GENERATION, out)
    return out


def payload_nbytes(contrib: Any) -> Optional[int]:
    """Wire size of a collective contribution for the bandwidth counters
    (rooted contributions arrive as ``(root, payload)`` tuples)."""
    if isinstance(contrib, tuple) and len(contrib) == 2:
        contrib = contrib[1]
    nb = getattr(contrib, "nbytes", None)
    if nb is None:
        return None
    dt = getattr(contrib, "dtype", None)
    if dt is None or dt == object:
        return None
    return int(nb)


# ---------------------------------------------------------------------------
# Hot-path counter hooks (call sites gate on enabled())
# ---------------------------------------------------------------------------

def add_send(comm: Any, nbytes: int, wait_ns: int = 0) -> None:
    acct = _acct(comm)
    if acct is None:
        return
    with _store_lock:
        acct.sends += 1
        acct.bytes_sent += int(nbytes or 0)
        acct.wait_ns += int(wait_ns)


def add_recv(comm: Any, nbytes: int, wait_ns: int = 0) -> None:
    acct = _acct(comm)
    if acct is None:
        return
    with _store_lock:
        acct.recvs += 1
        acct.bytes_recv += int(nbytes or 0)
        acct.wait_ns += int(wait_ns)


def add_wait(wait_s: float, comm: Any = None, cid: Optional[int] = None) -> None:
    """Time blocked in the Wait/Test family (unattributed waits land on the
    pseudo-cid -1)."""
    acct = _acct(comm, cid=cid)
    if acct is None:
        return
    with _store_lock:
        acct.wait_ns += int(wait_s * 1e9)


# -- wait-time ownership (the outermost-owner rule for wait_ns) -------------
#
# A persistent collective round is fully accounted by the op scope its
# worker (or the inline registered fast path) owns: the round's wall clock
# lands in ``times`` and its blocked share in ``phase_ns["rendezvous"]``.
# The caller blocked in ``Wait`` covers the SAME wall clock, so letting the
# inner ``CollRequest.wait`` also bump ``wait_ns`` double-counts it — the
# overhead_probe --pvars bug ISSUE-6 names. ``PersistentCollRequest`` claims
# ownership around its inner wait; nested add_wait callers check
# :func:`wait_owned` first and stand down.

def own_wait() -> bool:
    """Claim wait-time ownership for this thread. Returns True when the
    claim is fresh (caller must :func:`disown_wait` in a finally); False
    when an outer owner already holds it."""
    if _tls.wait_owned:
        return False
    _tls.wait_owned = True
    return True


def disown_wait() -> None:
    """Release the wait-time claim taken by :func:`own_wait`."""
    _tls.wait_owned = False


def wait_owned() -> bool:
    """True while an outer wait-time owner is on this thread's stack —
    nested waits must not call :func:`add_wait`."""
    return _tls.wait_owned


def note_rma(comm: Any, kind: str) -> None:
    """One RMA epoch event: kind in {fence, lock, flush}."""
    acct = _acct(comm)
    if acct is None:
        return
    with _store_lock:
        if kind in acct.rma:
            acct.rma[kind] += 1


def note_pipelined(cid: int, nchunks: int, fold_ns: int,
                   wait_after_first_ns: int) -> None:
    """One chunk-pipelined star fold at the root: the overlap-fraction
    inputs (fold time vs rendezvous waits AFTER the first chunk — waits
    that a perfectly overlapped pipeline hides behind the fold)."""
    acct = _acct(cid=cid)
    if acct is None:
        return
    with _store_lock:
        acct.pipe_ops += 1
        acct.pipe_chunks += int(nchunks)
        acct.pipe_fold_ns += int(fold_ns)
        acct.pipe_wait_ns += int(wait_after_first_ns)


def note_batch(cid: int, nops: int) -> None:
    """One batched-submission flush on this comm (ISSUE-11): ``nops``
    queued ops went through one rendezvous round trip."""
    acct = _acct(cid=cid)
    if acct is None:
        return
    with _store_lock:
        acct.batch_flushes += 1
        acct.batch_ops += int(nops)


# -- inference-engine block (tpu_mpi.infer) ----------------------------------
#
# Process-global (the engine spans every pool rank, so per-comm attribution
# would just smear one logical step over three comms): counters accumulate,
# gauges overwrite. Snapshot surfaces them as the top-level "infer" block
# next to plan_cache.

_infer: Dict[str, int] = {}
_infer_gauges: Dict[str, int] = {}


def note_infer(**counts: int) -> None:
    """Accumulate inference-engine counters (steps, tokens, batch_slots,
    prefills, step_ns, pwait_ns, stage_serial_ns, slo_hits/misses/
    evictions, ...)."""
    with _store_lock:
        for k, v in counts.items():
            _infer[k] = _infer.get(k, 0) + int(v)


def set_infer_gauges(**vals: int) -> None:
    """Overwrite inference-engine gauges (KV pressure, max_batch)."""
    with _store_lock:
        for k, v in vals.items():
            _infer_gauges[k] = int(v)


def infer_snapshot() -> dict:
    """The infer block of :func:`snapshot` (may be empty): accumulated
    counters plus the latest gauges under ``"gauges"``."""
    with _store_lock:
        if not _infer and not _infer_gauges:
            return {}
        return {**_infer, "gauges": dict(_infer_gauges)}


# -- training block (tpu_mpi.train) ------------------------------------------
#
# Process-global like the infer block: a training step spans every rank of
# the job, and the trainer lives above any single comm. Counters (steps,
# buckets, bucket_flushes, starts, waits, reshards, wait_ns,
# comm_window_ns, step_ns) accumulate; gauges (nbuckets, bucket_bytes,
# world) overwrite. A bounded per-step sample list feeds the stats
# renderer's p50/p99 without unbounded growth.

_train: Dict[str, int] = {}
_train_gauges: Dict[str, int] = {}
_train_steps: List[int] = []
_TRAIN_STEP_CAP = 4096


def note_train(**counts: int) -> None:
    """Accumulate training counters (steps, bucket_flushes, starts,
    waits, reshards, wait_ns, comm_window_ns, step_ns, ...)."""
    with _store_lock:
        for k, v in counts.items():
            _train[k] = _train.get(k, 0) + int(v)


def set_train_gauges(**vals: int) -> None:
    """Overwrite training gauges (nbuckets, bucket_bytes, world)."""
    with _store_lock:
        for k, v in vals.items():
            _train_gauges[k] = int(v)


def note_train_step(ns: int) -> None:
    """Record one optimizer-step duration sample (nanoseconds) for the
    p50/p99 rendering; also accumulates steps/step_ns counters."""
    with _store_lock:
        _train["steps"] = _train.get("steps", 0) + 1
        _train["step_ns"] = _train.get("step_ns", 0) + int(ns)
        if len(_train_steps) < _TRAIN_STEP_CAP:
            _train_steps.append(int(ns))


def train_snapshot() -> dict:
    """The train block of :func:`snapshot` (may be empty): accumulated
    counters, latest gauges under ``"gauges"``, and the bounded step-time
    sample list under ``"step_ns_samples"``."""
    with _store_lock:
        if not _train and not _train_gauges:
            return {}
        return {**_train, "gauges": dict(_train_gauges),
                "step_ns_samples": list(_train_steps)}


# -- elastic-capacity block (tpu_mpi.elastic) ---------------------------------
#
# Process-global like the infer block: resizes span the whole pool, so
# per-comm attribution is meaningless. Counters (resizes, rebinds, grown,
# shrunk, failures) accumulate; gauges (pool_size, target_size, degraded)
# overwrite.

_elastic: Dict[str, int] = {}
_elastic_gauges: Dict[str, int] = {}


def note_elastic(**counts: int) -> None:
    """Accumulate elastic-capacity counters (resizes, rebinds, grown,
    shrunk, failures, ...)."""
    with _store_lock:
        for k, v in counts.items():
            _elastic[k] = _elastic.get(k, 0) + int(v)


def set_elastic_gauges(**vals: int) -> None:
    """Overwrite elastic-capacity gauges (pool_size, target_size,
    degraded)."""
    with _store_lock:
        for k, v in vals.items():
            _elastic_gauges[k] = int(v)


def elastic_snapshot() -> dict:
    """The elastic block of :func:`snapshot` (may be empty): accumulated
    counters plus the latest gauges under ``"gauges"``."""
    with _store_lock:
        if not _elastic and not _elastic_gauges:
            return {}
        return {**_elastic, "gauges": dict(_elastic_gauges)}


# -- serve frame-path block (tpu_mpi.serve) ----------------------------------
#
# Process-global like the infer block: the session/mailbox frame path spans
# every tenant connection, so per-comm attribution would smear one wire hop
# over many comms. ``ops`` counts OP/RESULT frames carrying array payloads,
# ``copies`` counts payload materializations (ascontiguousarray / tobytes /
# non-view marshalling) on that path — the zero-copy acceptance gate is
# copies/ops <= 1 — ``sg_writes`` counts scatter-gather sendmsg calls and
# ``zc_bytes`` the payload bytes that travelled as views.

_serve_frame: Dict[str, int] = {}


def note_serve_frame(**counts: int) -> None:
    """Accumulate serve frame-path counters (ops, copies, sg_writes,
    zc_bytes, ...)."""
    with _store_lock:
        for k, v in counts.items():
            _serve_frame[k] = _serve_frame.get(k, 0) + int(v)


def serve_frame_snapshot() -> dict:
    """The serve_frame block of :func:`snapshot` (may be empty)."""
    with _store_lock:
        return dict(_serve_frame)


# -- front-door block (tpu_mpi.serve.frontdoor) ------------------------------
#
# Process-global like the serve_frame block: the event-driven session
# transport multiplexes every attached socket on one readiness loop, so
# per-comm attribution would smear loop mechanics over tenants. Counters
# accumulate (attaches, wakeups, frames, lease_hits/lease_misses/
# lease_drops, splice_bytes); gauges overwrite (open_sockets, workers,
# workers_busy).

_front_door: Dict[str, int] = {}
_front_door_gauges: Dict[str, int] = {}


def note_front_door(**counts: int) -> None:
    """Accumulate front-door counters (attaches, wakeups, frames,
    lease_hits, lease_misses, lease_drops, splice_bytes, ...)."""
    with _store_lock:
        for k, v in counts.items():
            _front_door[k] = _front_door.get(k, 0) + int(v)


def set_front_door_gauges(**vals: int) -> None:
    """Overwrite front-door gauges (open_sockets, workers, workers_busy)."""
    with _store_lock:
        for k, v in vals.items():
            _front_door_gauges[k] = int(v)


def front_door_snapshot() -> dict:
    """The front_door block of :func:`snapshot` (may be empty): accumulated
    counters plus the latest gauges under ``"gauges"``."""
    with _store_lock:
        if not _front_door and not _front_door_gauges:
            return {}
        return {**_front_door, "gauges": dict(_front_door_gauges)}


# -- lock-contention block (tpu_mpi.locksmith) -------------------------------
#
# Populated only when the lock witness is armed (TPU_MPI_LOCKCHECK=1):
# per named lock, how many acquisitions there were, how many had to wait
# behind another holder, and the longest single hold in nanoseconds.
# Process-global like the serve_frame block — lock names already carry
# their subsystem (``broker.dispatch``, ``pool.queues``, ...).

_locks: Dict[str, Dict[str, int]] = {}


def note_lock(name: str, acquires: int = 0, contended: int = 0,
              held_ns: int = 0) -> None:
    """Accumulate contention counters for one named lock. ``held_ns`` is
    a single observed hold time; the block keeps the max."""
    with _store_lock:
        row = _locks.get(name)
        if row is None:
            row = _locks[name] = {"acquires": 0, "contended": 0,
                                  "max_held_ns": 0}
        row["acquires"] += int(acquires)
        row["contended"] += int(contended)
        if held_ns > row["max_held_ns"]:
            row["max_held_ns"] = int(held_ns)


def locks_snapshot() -> dict:
    """The locks block of :func:`snapshot` (empty when the witness is off)."""
    with _store_lock:
        return {k: dict(v) for k, v in _locks.items()}


def note_explore(comm: Any, explored: bool) -> None:
    """One online-autotuner decision on this comm (tpu_mpi.tune_online):
    ``explored`` when the call was routed to an alternate arm."""
    acct = _acct(comm)
    if acct is None:
        return
    with _store_lock:
        acct.explore_calls += 1
        if explored:
            acct.explore_explored += 1


def note_swap(comm: Any, generation: int) -> None:
    """One online table hot-swap on this comm."""
    acct = _acct(comm)
    if acct is None:
        return
    with _store_lock:
        acct.table_swaps += 1
        acct.last_swap_gen = int(generation)


def arm_stats(comm: Any) -> List[Tuple[str, str, int, int, int]]:
    """This rank's accumulated latency stats on one comm as
    ``(coll, algo, nbytes, count, total_ns)`` rows — the payload the
    online autotuner's lockstep swap round allgathers so that every rank
    merges the IDENTICAL cross-rank arm statistics."""
    from ._runtime import current_env
    env = current_env()
    if env is None:
        return []
    key = (env[1], comm.cid)
    with _store_lock:
        acct = _store.get(key)
        if acct is None:
            return []
        return [(c, a, b, t[0], t[1])
                for (c, a, b), t in sorted(acct.times.items())]


# ---------------------------------------------------------------------------
# Snapshot / reset / dump
# ---------------------------------------------------------------------------

def _topology_stamp() -> str:
    """The ``topology_key`` of the world these counters describe — stamped
    into every dump record so ``tune merge`` can attribute samples to the
    right fabric without a side channel. Derived from the live context
    (domain map over the full world) when one is attached, else from
    config alone (a flat default — better unstamped-conservative than
    wrong)."""
    from . import topology as _topo
    try:
        from ._runtime import current_env
        env = current_env()
        if env is not None:
            ctx = env[0]
            n = int(getattr(ctx, "size", 0) or 0)
            if n >= 2:
                dom = _topo.domain_count(ctx, tuple(range(n)))
                return _topo.topology_key(dom, n)
    except Exception:
        pass
    return _topo.topology_key(int(config.load().domains), 0)


def snapshot(rank: Optional[int] = None, reset: bool = False) -> dict:
    """Machine-readable dump of every counter (one rank, or all ranks this
    process has accumulated). Stable schema — ``tpu_mpi.stats`` and
    ``tune.table_from_pvars`` consume exactly this."""
    global _store_gen
    from .overlap import plans
    with _store_lock:
        # cids mix ints and recovery tuples (("shrink", cid, epoch)) in one
        # store — sort through str so the dump order is still deterministic
        keys = [k for k in sorted(_store, key=lambda k: (k[0], str(k[1])))
                if rank is None or k[0] == rank]
        comms = [_store[k].snapshot() for k in keys]
        traced = {family: dict(_counts[family]) if isinstance(kinds, tuple)
                  else kinds(_counts[family])
                  for family, kinds in FAMILIES.items()}
        if reset:
            for k in keys:
                del _store[k]
            _store_gen += 1
    return {"schema": 1, "kind": "tpu_mpi-pvars", "level": level(),
            "topology": _topology_stamp(),
            "comms": comms, "plan_cache": plans.stats(),
            "arming_s": arming_seconds(),
            **traced,
            "build": build_snapshot(),
            "infer": infer_snapshot(), "train": train_snapshot(),
            "elastic": elastic_snapshot(),
            "serve_frame": serve_frame_snapshot(),
            "front_door": front_door_snapshot(),
            "locks": locks_snapshot()}


def comm_snapshot(comm: Any, reset: bool = False) -> dict:
    """``Comm.get_pvars`` backend: this rank's counters on one comm."""
    global _store_gen
    from ._runtime import require_env
    _, rank = require_env()
    key = (rank, comm.cid)
    with _store_lock:
        acct = _store.get(key)
        snap = acct.snapshot() if acct is not None \
            else CommPvars(rank, comm.cid).snapshot()
        if reset and acct is not None:
            del _store[key]
            _store_gen += 1
    return snap


def reset() -> None:
    """Drop every accumulated counter (all ranks of this process)."""
    global _store_gen
    with _store_lock:
        _store.clear()
        _infer.clear()
        _infer_gauges.clear()
        _train.clear()
        _train_gauges.clear()
        _train_steps.clear()
        _elastic.clear()
        _elastic_gauges.clear()
        _serve_frame.clear()
        _front_door.clear()
        _front_door_gauges.clear()
        _locks.clear()
        _arming.clear()
        _counts.update(_no_counts())
        _build_total[:] = [0, 0.0, 0, 0.0, 0, 0.0]
        _build_cache.update(hits=0, misses=0, load_s=0.0, saved_s=0.0)
        _build_by_fun.clear()
        _build_step.clear()
        _build_kernels.clear()
        _store_gen += 1


def dump(path: str, rank: Optional[int] = None, reset: bool = False) -> str:
    """Write :func:`snapshot` as JSON; returns the path."""
    rec = snapshot(rank=rank, reset=reset)
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
    with open(tmp, "w") as f:
        json.dump(rec, f, indent=1)
        f.write("\n")
    os.replace(tmp, path)
    return path


def load_dumps(paths) -> List[dict]:
    """Read pvar dump records from files and/or directories (a directory
    contributes every ``pvars-rank*.json`` / ``*.json`` file in it).
    Consumers: ``tpu_mpi.stats`` and ``tune.table_from_pvars``."""
    files: List[str] = []
    for p in paths:
        p = os.path.expanduser(p)
        if os.path.isdir(p):
            names = sorted(os.listdir(p))
            picked = [n for n in names if n.startswith("pvars-rank")
                      and n.endswith(".json")]
            files.extend(os.path.join(p, n) for n in
                         (picked or [n for n in names if n.endswith(".json")]))
        else:
            files.append(p)
    recs = []
    for f in files:
        with open(f) as fh:
            rec = json.load(fh)
        if rec.get("kind") != "tpu_mpi-pvars":
            raise ValueError(f"{f}: not a tpu_mpi pvar dump")
        rec["_path"] = f
        recs.append(rec)
    return recs


def finalize_dump(force: bool = False) -> Optional[str]:
    """Per-rank dump at Finalize (and at ``Pcontrol(level >= 2)``): when
    ``config.pvars_dump`` names a directory, this rank writes
    ``pvars-rank<R>.json`` there. Costs one branch when pvars are off."""
    if not (enabled() or force):
        return None
    from ._runtime import current_env
    d = config.load().pvars_dump
    if not d:
        return None
    env = current_env()
    rank = env[1] if env is not None else 0
    return dump(os.path.join(os.path.expanduser(d), f"pvars-rank{rank}.json"),
                rank=rank)
