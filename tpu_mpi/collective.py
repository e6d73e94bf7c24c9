"""Collectives over a communicator's rendezvous channel (host path).

Reference: /root/reference/src/collective.jl — Barrier (:15-19), Bcast! (:29-42)
+ serialized bcast (:44-60), Scatter(!*) (:90-129), Scatterv(!*) (:156-196),
Gather(!*) (:230-275), Allgather(!*) (:295-335), Gatherv(!*) (:363-403),
Allgatherv(!*) (:424-461), Alltoall(!*) (:489-532), Alltoallv(!*) (:545-578),
Reduce(!*) (:605-666), Allreduce(!*) (:691-738), Scan(!*) (:760-808),
Exscan(!*) (:834-882). Each exists in mutating, allocating, IN_PLACE and
scalar-object flavors; ``*v`` displacements are exclusive prefix sums.
``Reduce_scatter`` is absent in v0.14.2 — added here natively since XLA has it
(SURVEY.md §2.3 note).

API convention (Julia ``!`` does not exist in Python): one name per collective;
the *arity and argument kinds* select the flavor exactly as the reference's
method table does — ``Allreduce(send, op, comm)`` allocates,
``Allreduce(send, recv, op, comm)`` mutates, ``Allreduce(IN_PLACE, buf, op,
comm)`` is in-place; the scatter/gather family also accepts ``None`` for the
insignificant buffer like the reference accepts ``nothing``.

This is the *semantic* path, running over the thread rendezvous with zero-copy
shared-memory data placement. The compiled high-bandwidth path — the same
operations as XLA ICI collectives inside jit/shard_map — lives in
``tpu_mpi.xla`` (SURVEY.md §3.2: the whole stack collapses to one lax op).
"""

from __future__ import annotations

import functools
import pickle
import threading

from . import serialization as _serialization
from collections import OrderedDict
from typing import Any, Optional, Sequence

import numpy as np

from .buffers import (IN_PLACE, DeviceBuffer, _InPlace, assert_minlength,
                      clone_like, element_count, extract_array, is_jax_array,
                      on_sharding, to_wire, wire_view, write_flat)
from .comm import Comm, Intercomm, ROOT
from ._runtime import PROC_NULL
from . import error as _ec
from . import perfvars as _pv
from . import tune_online as _tune_online
from .analyze import events as _ev
from .error import CollectiveMismatchError, MPIError
from .operators import Op, as_op
from .overlap import (ChunkSchedule, CollectivePlan, PersistentCollRequest,
                      PlanRegistration, demote_fast_armed as _demote_fast_armed,
                      plans as _plans, progress_begin, progress_note,
                      registry as _registry)


def _run(comm: Comm, contrib: Any, combine, opname: str, plan=None,
         _sig=None) -> Any:
    # _ordered_run (defined with the nonblocking machinery below) keeps a
    # blocking collective from racing this rank's in-flight nonblocking
    # ones to the rendezvous: with outstanding work it runs through the
    # same single worker, preserving program order.
    # ``_sig`` is the trace verifier's precise cross-rank-checkable
    # signature (root/dtype/count) when the caller knows one.
    traced = _ev.enabled()
    # pvar op scope: channels drop phase spans into it; op_end stamps the
    # trace event and the per-comm counters. op_begin() returns None when an
    # outer owner (e.g. _reduce_family, capturing the copy-out phase too)
    # already opened one — then the owner finalizes, not us.
    sc = _pv.op_begin() if (traced or _pv.enabled()) else None
    try:
        if not traced:
            return _ordered_run(comm, lambda: comm.channel().run(
                comm.rank(), contrib, combine, opname, plan=plan))
        ev = _ev.record_collective(comm, opname, sig=_sig)
        if sc is not None:
            sc.ev = ev
        elif traced:
            outer = _pv.scope()
            if outer is not None and outer.ev is None:
                outer.ev = ev
        from ._runtime import require_env
        ctx, _ = require_env()
        bev = _ev.blocked_event(comm, "coll", opname)
        _ev.set_blocked(ctx, bev)
        try:
            return _ordered_run(comm, lambda: comm.channel().run(
                comm.rank(), contrib, combine, opname, plan=plan))
        finally:
            _ev.clear_blocked(ctx, bev)
    finally:
        if sc is not None:
            sig = _sig or {}
            # plan opnames carry the cid ("Allreduce@0") — strip for the key
            _pv.op_end(sc, comm, coll=opname.split("@", 1)[0].lower(),
                       algo=sig.get("algo"),
                       dtype=(str(sig["dtype"]) if sig.get("dtype") is not None
                              else None),
                       nbytes=_pv.payload_nbytes(contrib))


def _run_rooted(comm: Comm, root: int, contrib: Any, combine, opname: str,
                plan=None, _sig=None) -> Any:
    """Rendezvous for rooted collectives: every rank ships its claimed root
    inside its contribution, and divergent roots raise CollectiveMismatchError
    on all ranks instead of silently electing whoever arrives first (the
    Scatterv root-shipped-counts pattern, applied to the whole rooted family).
    ``combine(contribs, root)`` sees the validated root."""
    size = comm.size()
    if not isinstance(root, (int, np.integer)) or not (0 <= root < size):
        raise MPIError(f"invalid root {root!r} for a size-{size} communicator",
                       code=_ec.ERR_ROOT)
    root = int(root)

    def outer(cs):
        roots = sorted({r for r, _ in cs})
        if len(roots) > 1:
            raise CollectiveMismatchError(
                f"ranks disagree on the root of {opname}: {roots}")
        return combine([c for _, c in cs], roots[0])

    sig = dict(_sig or {})
    sig.setdefault("root", root)
    return _run(comm, (root, contrib), outer, opname, plan=plan, _sig=sig)


# Algorithm selections resolved this config generation, keyed on the full
# decision signature — one tune.select() (config read + table stat + table
# walk) per distinct collective shape instead of per call. Plans cache their
# selection too; this layer covers the plan-less collectives (Barrier,
# Bcast, the gather/scatter family).
_select_cache: "OrderedDict[Any, str]" = OrderedDict()
_SELECT_CAP = 512


def _coll_select(comm: Comm, coll: str, nbytes: Optional[int], *,
                 commutative: bool = False, elementwise: bool = False,
                 numeric: bool = True) -> str:
    """The collective-algorithm decision for one signature: ``tune.select``
    (force-override → measured tuning table → built-in heuristic) with this
    communicator's topology filled in (same-host shm eligibility from the
    rendezvous address table). The selection rides the plan to the
    multi-process tier and into the event IR (``sig["algo"]``); the thread
    tier shares one address space and always runs its in-process star, so
    there the recorded selection documents what the proc tier would do."""
    from . import backend as _backend
    from . import config as _config
    from . import topology as _topo
    from . import tune
    ctx = getattr(comm, "ctx", None)
    shm = False
    chk = getattr(ctx, "coll_shm_ok", None)
    if chk is not None:
        shm = bool(chk(comm.group))
    # hierarchy-usable domain count: rank-uniform (a function of the
    # member list, config.domains and the replicated address table), so
    # every rank of the communicator selects the same tier
    dom = _topo.domain_count(ctx, comm.group)
    # _RING_MIN_BYTES is a live module knob (tests move it mid-run to force
    # or suppress the bulk tiers) — key on it so the memo can't pin a
    # selection across a threshold change
    key = (comm.cid, coll, nbytes, commutative, elementwise, numeric, shm,
           dom, _config.GENERATION, _backend._RING_MIN_BYTES)
    algo = _select_cache.get(key)
    if algo is None:
        algo = tune.select(coll, comm.size(), nbytes, commutative=commutative,
                           elementwise=elementwise, shm=shm, numeric=numeric,
                           domains=dom)
        _select_cache[key] = algo
        while len(_select_cache) > _SELECT_CAP:
            _select_cache.popitem(last=False)
    return algo


def _maybe_explore(comm: Comm, coll: str, nbytes: Optional[int], algo: str, *,
                   commutative: bool = False, elementwise: bool = False,
                   numeric: bool = True) -> str:
    """Online-autotuner hook at the decision point (docs/performance.md
    "Online tuning"): with exploration off — the default — this costs one
    generation-cached tuple compare; with it on, the bandit may reroute
    this call to an eligible alternate arm on its deterministic lockstep
    schedule. Called exactly once per user-facing collective call (never
    from plan build or registration), so the shared counters advance
    identically on every rank."""
    st = _tune_online.state()
    if st is None:
        return algo
    from . import topology as _topo
    ctx = getattr(comm, "ctx", None)
    chk = getattr(ctx, "coll_shm_ok", None)
    shm = bool(chk(comm.group)) if chk is not None else False
    dom = _topo.domain_count(ctx, comm.group)
    return st.decide(comm, coll, nbytes, algo, commutative=commutative,
                     elementwise=elementwise, numeric=numeric, shm=shm,
                     domains=dom)


def _wire_nbytes(payload: Any) -> Optional[int]:
    """Payload size for the algorithm decision: bytes when the wire payload
    is a fixed-dtype array, None (size unknown / object payload) otherwise.
    Must be rank-uniform — callers only pass buffers whose count and dtype
    the MPI contract replicates."""
    dt = getattr(payload, "dtype", None)
    if dt is None or dt == object:
        return None
    return int(getattr(payload, "nbytes", 0))


_NOT_JITTABLE = object()

# Compiled-fold caches, keyed by the *underlying fn* so that as_op() wrapping
# the same user function in a fresh Op each call still hits. Bounded LRU:
# compiled executables are retained for at most _FOLD_CAP distinct
# (fn, mode, nranks, dtype, shapes) signatures. A signature is only compiled
# on its SECOND encounter (_fold_seen), so a one-shot lambda never pays the
# trace+compile cost — it runs the eager fold like before.
_FOLD_CAP = 64
_fold_compiled: "OrderedDict[Any, Any]" = OrderedDict()
_fold_seen: "OrderedDict[Any, None]" = OrderedDict()
_fold_lock = threading.Lock()
# The fold executables that span the ranks' chips (_exchange_fold), one per
# (fn, count, dtype, devices) and process: any rank is a round's last
# arriver, so every rank's plan of a signature holds the same one.
_exchange_compiled: "OrderedDict[Any, list]" = OrderedDict()
# A multi-device executable is enqueued device by device. Two of them
# enqueued at once from two threads (two communicators' last arrivers) could
# reach two chips in opposite orders, and their collectives would wait for
# each other: held around the enqueue only, never for the device's work.
_exchange_launch = threading.Lock()


def _traceable(fold, *avals) -> bool:
    """Whether a fold over ``op.fn`` traces at all. A host-only user
    operator (one that calls numpy or branches on values) is the documented
    reason a device fold is declined; it fails HERE, abstractly, before
    anything is lowered — so whatever raises later, in lowering or
    compilation, is the device's failure and propagates."""
    import jax
    try:
        jax.eval_shape(fold, *avals)
    except Exception:       # noqa: BLE001 - arbitrary user code under trace
        return False
    return True


def _colocated(arrs: Sequence[Any]) -> Optional[Sequence[Any]]:
    """The operands of one device fold on ONE device, or None when they are
    not all jax arrays. Each rank's buffer lives on its own chip
    (``Comm.device``) and XLA refuses a computation whose arguments span
    devices: the fold runs where rank 0's contribution lives and the others
    arrive by device-to-device copy (a star; every rank's copy-out then
    moves the result home). One chip (or CPU-sim default placement): every
    operand is already there and nothing moves. What still takes this route
    across chips: an Allreduce before its streak arms, Reduce, Scan and the
    registered lane of ranks that share a chip. The armed Allreduce of
    ranks on chips of their own does not (:func:`_exchange_fold`)."""
    if not arrs or not all(is_jax_array(a) for a in arrs):
        return None
    return _colocate(arrs, arrs[0].sharding)


def _colocate(arrs: Sequence[Any], home: Any) -> list:
    """``arrs`` on ``home``, each that is elsewhere by a copy enqueued here.
    Inside an op scope the copies between chips are counted; where the op
    publishes its span tree the enqueueing is its ``colocate`` span (a child
    of the fold's dispatch) and the watcher stamps when the last copy had
    arrived (:func:`_watch_fold`)."""
    sc = _pv.scope()
    if sc is None:
        return [on_sharding(a, home) for a in arrs]
    t0 = _pv.monotonic() if sc.tree else 0.0
    out = []
    for a in arrs:
        if a.sharding == home:
            out.append(a)
        else:
            _pv.note_moved(sc, True, a.nbytes)
            out.append(on_sharding(a, home))
    if sc.tree:
        sc.nested = (sc.nested or []) + [("colocate", t0, _pv.monotonic())]
    return out


def _watch_fold(operands: Sequence[Any], out: Any,
                t0: Optional[float] = None) -> None:
    """The device's end of a fold (see ``perfvars.watch``), one watch a
    round. Where operands crossed chips: from the start of their
    ``colocate`` span to when they had all arrived, and to when the fold's
    output was ready. Where none did, only a registered fold is watched
    (it gives ``t0``, where its combine began): its output alone, never the
    operands, so nothing of a rank's is held beyond the result it gets."""
    sc = _pv.scope()
    if sc is None or not sc.tree:
        return
    if sc.moved_in is not None:
        t0 = sc.nested[-1][1]       # the start of their ``colocate``
        _pv.watch(sc, t0, ("copy_in.done", list(operands)),
                  ("fold.done", out))
    elif t0 is not None:
        _pv.watch(sc, t0, ("fold.done", out))


def _concat(parts: Sequence[Any], home: Any = None) -> Any:
    """Flat concatenation of per-rank pieces on the array kind they came in:
    numpy on the host, or — if any piece is a device array — one on-device
    concatenate where ``home`` lives (default: the first device piece). The
    pieces of a multi-chip job sit on their senders' chips; each is moved
    to ``home``'s by device-to-device copy (host pieces by upload)."""
    dev = [p for p in parts if is_jax_array(p)]
    if not dev:
        return np.concatenate([np.asarray(p).reshape(-1) for p in parts])
    import jax.numpy as jnp
    sh = (home if is_jax_array(home) else dev[0]).sharding
    return jnp.concatenate([on_sharding(p, sh).reshape(-1) for p in parts])


def _left_chain(op: Op):
    """``op`` over any number of operands as the rank-ordered left chain:
    what every device fold of the host path computes."""
    def plain_fold(*xs):
        acc = xs[0]
        for x in xs[1:]:
            acc = op.fn(acc, x)
        return acc
    return plain_fold


def _jitted_fold(arrs: Sequence[Any], op: Op, mode: str):
    """One-dispatch combine for co-located device arrays: the whole
    rank-ordered fold is compiled into a single XLA computation (fused: one
    pass over the operands instead of n-1 round trips through HBM — the hot
    loop the reference gets from libmpi's tuned ring,
    src/collective.jl:691-738). Sequential left fold, so results are
    bit-identical to the eager rank-order reduction.

    Returns the combined array ("reduce"), the tuple of inclusive prefixes
    ("scan"), or _NOT_JITTABLE when the op can't trace (host-only custom fn)
    or the signature isn't worth compiling yet. A fold that traces and then
    fails to lower or compile raises."""
    n = len(arrs)
    if n <= 1:
        return _NOT_JITTABLE
    try:
        key = (op.fn, mode, n, str(arrs[0].dtype), tuple(a.shape for a in arrs))
        hash(key)
    except TypeError:
        return _NOT_JITTABLE
    with _fold_lock:
        hit = _fold_compiled.get(key)
        if hit is None:
            if key not in _fold_seen:
                _fold_seen[key] = None
                while len(_fold_seen) > 4 * _FOLD_CAP:
                    _fold_seen.popitem(last=False)
                return _NOT_JITTABLE
    if hit is _NOT_JITTABLE:
        return _NOT_JITTABLE
    if hit is not None:
        return hit(*arrs)

    import jax

    if mode == "reduce":
        fold = _left_chain(op)
    else:  # scan: all inclusive prefixes
        def fold(*xs):
            outs = [xs[0]]
            for x in xs[1:]:
                outs.append(op.fn(outs[-1], x))
            return tuple(outs)
    jitted = out = _NOT_JITTABLE
    if _traceable(fold, *arrs):
        jitted = jax.jit(fold)
        with _pv.setup_span("jitted_fold.compile",
                            function=fold.__name__, mode=mode):
            out = jitted(*arrs)
    with _fold_lock:
        _fold_compiled[key] = jitted
        while len(_fold_compiled) > _FOLD_CAP:
            _fold_compiled.popitem(last=False)
    return out


def _reduce_arrays(arrs: Sequence[Any], op: Op,
                   schedule: Optional[ChunkSchedule] = None) -> Any:
    """Rank-ordered elementwise reduction (deterministic; MPI rank order).
    Device operands fold on the device: one compiled computation, or — the
    first time a signature is seen, and for a user operator that cannot be
    traced — ``op`` applied pairwise to the arrays where they live.
    With a chunk ``schedule`` (overlap engine), host folds run chunk-by-chunk
    — cache-resident working set, progress notes per chunk, and on the
    multi-process tier the per-chunk structure is what lets the star root
    fold chunk k while the drainer still receives chunk k+1."""
    dev = _colocated(arrs)
    if dev is not None:
        out = _jitted_fold(dev, op, "reduce")
        if out is _NOT_JITTABLE:
            out = functools.reduce(op, dev)
        _watch_fold(dev, out)
        return out
    if schedule is not None and len(arrs) > 1:
        out = _chunked_fold(arrs, op, schedule)
        if out is not None:
            return out
    return functools.reduce(op, arrs)


def _chunked_fold(arrs: Sequence[Any], op: Op,
                  schedule: ChunkSchedule) -> Optional[Any]:
    """Chunk-pipelined host fold. Elementwise rank-order folds are
    chunk-separable, so this is BITWISE-IDENTICAL to the monolithic
    ``functools.reduce``: ufunc-backed ops (SUM/PROD/MIN/MAX/B*) fold each
    chunk in place into one preallocated output (zero temporaries — the
    monolithic fold allocates n-1 full-size intermediates); other
    elementwise ops fold per-chunk and concatenate, preserving the exact
    dtype-promotion behavior. Returns None when the operands don't fit
    (non-numpy, object dtype, ragged sizes) and the caller's monolithic
    fold applies."""
    from .operators import is_elementwise
    if not is_elementwise(op):
        return None     # unknown custom fn might couple elements: monolithic
    first = arrs[0]
    if any(not isinstance(a, np.ndarray) or a.dtype == object for a in arrs):
        return None
    if any(a.size != schedule.count for a in arrs):
        return None
    flats = [a.reshape(-1) for a in arrs]
    prog = progress_begin(schedule.nchunks, "fold")
    if op.ufunc is not None and all(a.dtype == first.dtype for a in arrs):
        out = np.empty(schedule.count, dtype=first.dtype)
        for lo, hi in schedule:
            np.copyto(out[lo:hi], flats[0][lo:hi])
            for a in flats[1:]:
                op.ufunc(out[lo:hi], a[lo:hi], out=out[lo:hi])
            progress_note(prog)
        return out
    parts = []
    for lo, hi in schedule:
        parts.append(functools.reduce(op, [a[lo:hi] for a in flats]))
        progress_note(prog)
    return np.concatenate(parts)


def _scan_arrays(cs: Sequence[Any], op: Op) -> list:
    """Inclusive prefixes in rank order (same fold, all partials kept)."""
    dev = _colocated(cs)
    if dev is not None:
        cs = dev
        pre = _jitted_fold(cs, op, "scan")
        if pre is not _NOT_JITTABLE:
            return list(pre)
    outs: list = []
    acc = None
    for c in cs:
        acc = c if acc is None else op(acc, c)
        outs.append(acc)
    return outs


def _is_none(x: Any) -> bool:
    return x is None or isinstance(x, _InPlace)


# ---------------------------------------------------------------------------
# Intercommunicator collectives (MPI_ROOT semantics; VERDICT r3 #8).
# The reference reaches these through libmpi, which honors collectives on the
# intercomms Comm_spawn creates (/root/reference/src/comm.jl:135-162). Here
# they run over the intercomm's two-group rendezvous: in the ROOT GROUP the
# sourcing rank passes MPI.ROOT and the rest pass MPI.PROC_NULL; the RECEIVING
# group passes the root's rank within the remote group.
# ---------------------------------------------------------------------------

def _inter_rooted(comm: Intercomm, root: Any, payload: Any, opname: str):
    """Two-group rooted rendezvous. Returns (got_value, value): got_value is
    True only for receiving-group ranks."""
    chan, slot, a, b = comm.two_group_channel()
    in_a = slot < len(a)
    if root == ROOT:
        contrib = ("root", payload, in_a)
    elif root == PROC_NULL:
        contrib = ("null", None, in_a)
    else:
        r = int(root)
        if not (0 <= r < comm.remote_size()):
            raise MPIError(f"invalid intercomm root {root!r}: pass MPI.ROOT "
                           f"(source), MPI.PROC_NULL (non-source, root group) "
                           f"or a remote-group rank < {comm.remote_size()}",
                           code=_ec.ERR_ROOT)
        contrib = ("recv", r, in_a)

    def combine(cs):
        roots = [i for i, c in enumerate(cs) if c[0] == "root"]
        if len(roots) != 1:
            raise CollectiveMismatchError(
                f"{opname}: exactly one rank must pass MPI.ROOT, got "
                f"{len(roots)}")
        ri = roots[0]
        root_in_a = cs[ri][2]
        root_idx = ri if root_in_a else ri - len(a)
        out = []
        for i, (role, val, ia) in enumerate(cs):
            if role == "root":
                out.append((False, None))
            elif role == "null":
                if ia != root_in_a:
                    raise CollectiveMismatchError(
                        f"{opname}: rank in the receiving group passed "
                        f"MPI.PROC_NULL; receivers must pass the root's "
                        f"remote-group rank")
                out.append((False, None))
            else:
                if ia == root_in_a:
                    raise CollectiveMismatchError(
                        f"{opname}: rank in the root group passed a root rank "
                        f"({val}); non-source root-group ranks pass "
                        f"MPI.PROC_NULL")
                if val != root_idx:
                    raise CollectiveMismatchError(
                        f"{opname}: receiving group names root {val} but the "
                        f"source is remote-group rank {root_idx}")
                out.append((True, cs[ri][1]))
        return out

    return _ordered_run(comm, lambda: chan.run(slot, contrib, combine, opname))


def _inter_barrier(comm: Intercomm) -> None:
    chan, slot, a, b = comm.two_group_channel()
    _ordered_run(comm, lambda: chan.run(
        slot, None, lambda cs: [None] * len(cs), f"IBarrier@{comm.cid}"))


def _inter_bcast_buf(buf: Any, count: Optional[int], root: Any,
                     comm: Intercomm) -> Any:
    opname = f"InterBcast@{comm.cid}"
    if root == ROOT:
        n = element_count(buf) if count is None else count
        assert_minlength(buf, n)
        _inter_rooted(comm, root, (to_wire(buf, n), n), opname)
        return buf
    got, res = _inter_rooted(comm, root, None, opname)
    if got:
        val, n_src = res
        n = n_src if count is None else count
        assert_minlength(buf, n)
        write_flat(buf, val, n)
    return buf


def _inter_bcast_obj(obj: Any, root: Any, comm: Intercomm) -> Any:
    opname = f"interbcast@{comm.cid}"
    if root == ROOT:
        try:
            payload = ("pickle", _serialization.dumps(obj))
        except Exception:
            payload = ("ref", obj)
        _inter_rooted(comm, root, payload, opname)
        return obj
    got, res = _inter_rooted(comm, root, None, opname)
    if not got:
        return obj        # PROC_NULL participant: argument untouched
    kind, data = res
    return pickle.loads(data) if kind == "pickle" else data


# ---------------------------------------------------------------------------
# Barrier
# ---------------------------------------------------------------------------

def Barrier(comm: Comm) -> None:
    """Block until every rank of comm arrives (src/collective.jl:15-19).
    On an intercommunicator: until every rank of BOTH groups arrives."""
    if isinstance(comm, Intercomm):
        return _inter_barrier(comm)
    algo = _maybe_explore(comm, "barrier", None,
                          _coll_select(comm, "barrier", None))
    _run(comm, None, lambda cs: [None] * len(cs), f"Barrier@{comm.cid}",
         plan=("barrier", algo), _sig={"algo": algo})


# ---------------------------------------------------------------------------
# Bcast / bcast
# ---------------------------------------------------------------------------

def Bcast(buf: Any, *args) -> Any:
    """``Bcast(buf, [count,] root, comm)`` — broadcast root's buffer into every
    rank's buffer, mutating (src/collective.jl:29-42). Returns buf."""
    if len(args) == 2:
        count, (root, comm) = None, args
    elif len(args) == 3:
        count, root, comm = args
    else:
        raise TypeError("Bcast(buf, [count,] root, comm)")
    if isinstance(comm, Intercomm):
        return _inter_bcast_buf(buf, count, root, comm)
    rank = comm.rank()
    n = element_count(buf) if count is None else count
    assert_minlength(buf, n)
    payload = to_wire(buf, n) if rank == root else None

    def combine(cs, rt):
        val = cs[rt]
        return [val] * len(cs)

    dt = getattr(extract_array(buf), "dtype", None)
    nbytes = int(n) * dt.itemsize if dt is not None and dt != object else None
    algo = _maybe_explore(
        comm, "bcast", nbytes,
        _coll_select(comm, "bcast", nbytes, numeric=nbytes is not None),
        numeric=nbytes is not None)
    val = _run_rooted(comm, root, payload, combine, f"Bcast@{comm.cid}",
                      plan=("bcast", root, algo),
                      _sig={"count": int(n), "dtype": str(dt), "algo": algo})
    if rank != root:
        write_flat(buf, val, n)
    return buf


def bcast(obj: Any, root: int, comm: Comm) -> Any:
    """Broadcast an arbitrary serialized object (src/collective.jl:44-60).

    The reference's two-phase length+payload dance collapses: the rendezvous
    carries dynamic sizes natively. Serialization round-trips give each rank
    its own copy; closures/lambdas/local classes travel by value on every
    tier via :mod:`tpu_mpi.serialization` (ref broadcasts a *function*,
    test/test_bcast.jl:38-55). Truly unserializable objects (sockets,
    locks) fall back to by-reference sharing, thread tier only."""
    if isinstance(comm, Intercomm):
        return _inter_bcast_obj(obj, root, comm)
    rank = comm.rank()
    if rank == root:
        try:
            payload = ("pickle", _serialization.dumps(obj))
        except Exception:
            payload = ("ref", obj)
    else:
        payload = None

    def combine(cs, rt):
        val = cs[rt]
        return [val] * len(cs)

    algo = _maybe_explore(comm, "bcast", None,
                          _coll_select(comm, "bcast", None, numeric=False),
                          numeric=False)
    kind, data = _run_rooted(comm, root, payload, combine, f"bcast@{comm.cid}",
                             plan=("bcast", root, algo), _sig={"algo": algo})
    if rank == root:
        return obj
    return pickle.loads(data) if kind == "pickle" else data


# ---------------------------------------------------------------------------
# Scatter / Scatterv
# ---------------------------------------------------------------------------

def Scatter(*args) -> Any:
    """``Scatter(send, recv, [count,] root, comm)`` mutating |
    ``Scatter(send, count, root, comm)`` allocating (src/collective.jl:90-129).
    Root's send buffer is split into comm-size equal chunks in rank order;
    ``None``/IN_PLACE marks the insignificant buffer."""
    if len(args) == 5:
        sendbuf, recvbuf, count, root, comm = args
        alloc = False
    elif len(args) == 4 and isinstance(args[1], (int, np.integer)):
        sendbuf, count, root, comm = args
        recvbuf, alloc = None, True
    elif len(args) == 4:
        sendbuf, recvbuf, root, comm = args
        count, alloc = None, False
    else:
        raise TypeError("Scatter(send, recv, [count,] root, comm) or Scatter(send, count, root, comm)")
    rank, size = comm.rank(), comm.size()
    isroot = rank == root
    if count is None and not alloc:
        count = element_count(recvbuf) if not _is_none(recvbuf) else element_count(sendbuf) // size
    if isroot:
        if _is_none(sendbuf):
            raise MPIError("root must supply a send buffer to Scatter")
        assert_minlength(sendbuf, count * size)
    if not alloc and not (isroot and _is_none(recvbuf)):
        assert_minlength(recvbuf, count)   # before the rendezvous (see Gather)
    payload = to_wire(sendbuf, count * size) if isroot else None

    def combine(cs, rt):
        data = cs[rt]
        return [data[r * count:(r + 1) * count] for r in range(len(cs))]

    # The decision size must be rank-uniform: in the allocating flavor only
    # the root holds a buffer, so size-blind selection (None) keeps every
    # rank on the same algorithm.
    if alloc:
        nbytes = None
    else:
        dt = getattr(extract_array(sendbuf if isroot else recvbuf),
                     "dtype", None)
        nbytes = (count * size * dt.itemsize
                  if dt is not None and dt != object else None)
    algo = _maybe_explore(comm, "scatter", nbytes,
                          _coll_select(comm, "scatter", nbytes))
    chunk = _run_rooted(comm, root, payload, combine, f"Scatter@{comm.cid}",
                        plan=("scatter", algo), _sig={"algo": algo})
    if alloc:
        template = sendbuf if isroot else None
        return clone_like(template, chunk) if template is not None else np.array(chunk)
    if isroot and _is_none(recvbuf):
        return sendbuf          # IN_PLACE at root: data already in place
    write_flat(recvbuf, chunk, count)
    return recvbuf


def Scatterv(*args) -> Any:
    """``Scatterv(send, recv, counts, root, comm)`` mutating |
    ``Scatterv(send, counts, root, comm)`` allocating (src/collective.jl:156-196).
    Displacements are the exclusive prefix sum of counts (:169)."""
    if len(args) == 5:
        sendbuf, recvbuf, counts, root, comm = args
        alloc = False
    elif len(args) == 4:
        sendbuf, counts, root, comm = args
        recvbuf, alloc = None, True
    else:
        raise TypeError("Scatterv(send, [recv,] counts, root, comm)")
    rank, size = comm.rank(), comm.size()
    isroot = rank == root
    counts = [int(c) for c in counts]
    if isroot:
        if _is_none(sendbuf):
            raise MPIError("root must supply a send buffer to Scatterv")
        assert_minlength(sendbuf, sum(counts))
    # counts are significant only at the root (MPI semantics): ship them in
    # the root's contribution so a divergent non-root list cannot influence
    # the slicing depending on rendezvous arrival order.
    payload = (to_wire(sendbuf, sum(counts)), counts) if isroot else None

    def combine(cs, rt):
        data, root_counts = cs[rt]
        displs = np.concatenate([[0], np.cumsum(root_counts)])
        return [data[displs[r]:displs[r] + root_counts[r]] for r in range(len(cs))]

    chunk = _run_rooted(comm, root, payload, combine, f"Scatterv@{comm.cid}")
    if alloc:
        template = sendbuf if isroot else None
        return clone_like(template, chunk) if template is not None else np.array(chunk)
    if isroot and _is_none(recvbuf):
        return sendbuf
    n = int(np.asarray(chunk).size)
    assert_minlength(recvbuf, n)
    write_flat(recvbuf, chunk, n)
    return recvbuf


# ---------------------------------------------------------------------------
# Gather / Gatherv / Allgather / Allgatherv
# ---------------------------------------------------------------------------

def Gather(*args) -> Any:
    """``Gather(send, recv, [count,] root, comm)`` mutating |
    ``Gather(send, [count,] root, comm)`` allocating — works for arrays and
    scalar objects (src/collective.jl:230-275)."""
    if len(args) == 5:
        sendbuf, recvbuf, count, root, comm = args
        alloc = False
    elif len(args) == 4 and isinstance(args[1], (int, np.integer)):
        sendbuf, count, root, comm = args
        recvbuf, alloc = None, True
    elif len(args) == 4:
        sendbuf, recvbuf, root, comm = args
        count, alloc = None, False
    elif len(args) == 3:
        sendbuf, root, comm = args
        recvbuf, count, alloc = None, None, True
    else:
        raise TypeError("Gather(send, [recv,] [count,] root, comm)")
    return _gather_impl(sendbuf, recvbuf, count, root, comm, alloc, all_ranks=False)


def Allgather(*args) -> Any:
    """``Allgather(send, recv, count, comm)`` | ``Allgather(IN_PLACE, buf,
    count, comm)`` | ``Allgather(send, [count,] comm)`` allocating
    (src/collective.jl:295-335). Every rank receives the concatenation."""
    if len(args) == 4:
        sendbuf, recvbuf, count, comm = args
        alloc = False
    elif len(args) == 3 and isinstance(args[1], (int, np.integer)):
        sendbuf, count, comm = args
        recvbuf, alloc = None, True
    elif len(args) == 2:
        sendbuf, comm = args
        recvbuf, count, alloc = None, None, True
    else:
        raise TypeError("Allgather(send, [recv,] [count,] comm)")
    return _gather_impl(sendbuf, recvbuf, count, None, comm, alloc, all_ranks=True)


def _gather_impl(sendbuf, recvbuf, count, root, comm, alloc, all_ranks):
    rank, size = comm.rank(), comm.size()
    isroot = all_ranks or rank == root
    inplace = isinstance(sendbuf, _InPlace) or sendbuf is None
    if inplace:
        # IN_PLACE: rank's own chunk already sits at recvbuf[rank*count:...]
        # (src/collective.jl:309-313 in-place Allgather!).
        if _is_none(recvbuf):
            raise MPIError("IN_PLACE gather needs the send-recv buffer")
        if count is None:
            count = element_count(recvbuf) // size
        arr = to_wire(recvbuf, element_count(recvbuf))
        payload = arr.reshape(-1)[rank * count:(rank + 1) * count]
    else:
        if count is None:
            count = element_count(sendbuf)
        assert_minlength(sendbuf, count)
        payload = to_wire(sendbuf, count)
    # Bounds-check the significant recv buffer *before* the rendezvous, like
    # the reference checks before the ccall (src/collective.jl:230-275) — a
    # failing rank must not have half-entered the collective.
    if not alloc and isroot and not _is_none(recvbuf):
        assert_minlength(recvbuf, count * size)

    def combine(cs, rt=None):
        full = _concat(cs, cs[rt or 0])
        if rt is None:                  # Allgather: everyone needs it
            return [full] * len(cs)
        # rooted Gather: only root receives the concatenation — on the
        # multi-process star this keeps egress at ~zero instead of P×payload
        # (VERDICT r2 weak #6; src/collective.jl:230-275 root-only recvbuf)
        return [full if r == rt else None for r in range(len(cs))]

    nb = _wire_nbytes(payload)
    if all_ranks:
        # multi-process tier: big uniform blocks travel a ring (one hop per
        # block per step) instead of star ingress + P x egress at the root;
        # the selection is keyed on the per-rank block size, matching the
        # ring's per-hop cost
        algo = _maybe_explore(
            comm, "allgather", nb,
            _coll_select(comm, "allgather", nb, numeric=nb is not None),
            numeric=nb is not None)
        full = _run(comm, payload, combine, f"Allgather@{comm.cid}",
                    plan=("allgather", algo), _sig={"algo": algo})
    else:
        gnb = nb * size if nb is not None else None
        algo = _maybe_explore(comm, "gather", gnb,
                              _coll_select(comm, "gather", gnb))
        full = _run_rooted(comm, root, payload, combine, f"Gather@{comm.cid}",
                           plan=("gather", algo), _sig={"algo": algo})
    if not isroot:
        return None if alloc else recvbuf
    if alloc:
        template = sendbuf if not inplace else recvbuf
        return clone_like(template, full)
    write_flat(recvbuf, full, count * size)
    return recvbuf


def Gatherv(*args) -> Any:
    """``Gatherv(send, recv, counts, root, comm)`` mutating |
    ``Gatherv(send, counts, root, comm)`` allocating (src/collective.jl:363-403)."""
    if len(args) == 5:
        sendbuf, recvbuf, counts, root, comm = args
        alloc = False
    elif len(args) == 4:
        sendbuf, counts, root, comm = args
        recvbuf, alloc = None, True
    else:
        raise TypeError("Gatherv(send, [recv,] counts, root, comm)")
    return _gatherv_impl(sendbuf, recvbuf, counts, root, comm, alloc, all_ranks=False)


def Allgatherv(*args) -> Any:
    """``Allgatherv(send, recv, counts, comm)`` | ``Allgatherv(IN_PLACE, buf,
    counts, comm)`` | allocating ``Allgatherv(send, counts, comm)``
    (src/collective.jl:424-461)."""
    if len(args) == 4:
        sendbuf, recvbuf, counts, comm = args
        alloc = False
    elif len(args) == 3:
        sendbuf, counts, comm = args
        recvbuf, alloc = None, True
    else:
        raise TypeError("Allgatherv(send, [recv,] counts, comm)")
    return _gatherv_impl(sendbuf, recvbuf, counts, None, comm, alloc, all_ranks=True)


def _gatherv_impl(sendbuf, recvbuf, counts, root, comm, alloc, all_ranks):
    rank, size = comm.rank(), comm.size()
    isroot = all_ranks or rank == root
    counts = [int(c) for c in counts]
    displs = np.concatenate([[0], np.cumsum(counts)])  # exclusive prefix (:365,:425)
    inplace = isinstance(sendbuf, _InPlace) or sendbuf is None
    if inplace:
        if _is_none(recvbuf):
            raise MPIError("IN_PLACE gatherv needs the send-recv buffer")
        arr = to_wire(recvbuf, element_count(recvbuf))
        payload = arr.reshape(-1)[displs[rank]:displs[rank] + counts[rank]]
    else:
        assert_minlength(sendbuf, counts[rank])
        payload = to_wire(sendbuf, counts[rank])
    if not alloc and isroot and not _is_none(recvbuf):
        assert_minlength(recvbuf, sum(counts))   # before the rendezvous

    def combine(cs, rt=None):
        full = _concat(cs, cs[rt or 0])
        if rt is None:                  # Allgatherv: everyone needs it
            return [full] * len(cs)
        # rooted Gatherv: root-only result (VERDICT r2 weak #6)
        return [full if r == rt else None for r in range(len(cs))]

    if all_ranks:
        # ragged ring tier (multi-process): the counts list is replicated by
        # the API contract, so a size gate on the TOTAL is deterministic
        # across ranks even though per-rank blocks differ
        total_bytes = int(sum(counts)) * getattr(
            getattr(payload, "dtype", None), "itemsize", 0)
        dt = getattr(payload, "dtype", None)
        numeric = dt is not None and dt != object
        gnb = total_bytes if numeric else None
        algo = _maybe_explore(comm, "allgatherv", gnb,
                              _coll_select(comm, "allgatherv", gnb,
                                           numeric=numeric),
                              numeric=numeric)
        full = _run(comm, payload, combine, f"Allgatherv@{comm.cid}",
                    plan=("allgatherv", total_bytes, tuple(counts), algo),
                    _sig={"algo": algo})
    else:
        full = _run_rooted(comm, root, payload, combine, f"Gatherv@{comm.cid}")
    if not isroot:
        return None if alloc else recvbuf
    if alloc:
        template = sendbuf if not inplace else recvbuf
        return clone_like(template, full)
    write_flat(recvbuf, full, sum(counts))
    return recvbuf


# ---------------------------------------------------------------------------
# Alltoall / Alltoallv
# ---------------------------------------------------------------------------

def Alltoall(*args) -> Any:
    """``Alltoall(send, recv, count, comm)`` | ``Alltoall(IN_PLACE, buf, count,
    comm)`` | allocating ``Alltoall(send, count, comm)``
    (src/collective.jl:489-532). Rank r sends its chunk j to rank j's slot r."""
    if len(args) == 4:
        sendbuf, recvbuf, count, comm = args
        alloc = False
    elif len(args) == 3:
        sendbuf, count, comm = args
        recvbuf, alloc = None, True
    else:
        raise TypeError("Alltoall(send, [recv,] count, comm)")
    rank, size = comm.rank(), comm.size()
    count = int(count)
    inplace = isinstance(sendbuf, _InPlace) or sendbuf is None
    src = recvbuf if inplace else sendbuf
    assert_minlength(src, count * size)
    if not alloc and not inplace:
        assert_minlength(recvbuf, count * size)   # before the rendezvous
    payload = to_wire(src, count * size)

    def combine(cs):
        # rank r's result is assembled on rank r's own chip
        mats = [c.reshape(len(cs), count) for c in cs]
        return [_concat([m[r] for m in mats], cs[r]) for r in range(len(cs))]

    # multi-process tier: large exchanges go direct pairwise (each segment
    # one hop) instead of O(P²·seg) through the star root
    nb = _wire_nbytes(payload)
    algo = _maybe_explore(
        comm, "alltoall", nb,
        _coll_select(comm, "alltoall", nb, numeric=nb is not None),
        numeric=nb is not None)
    mine = _run(comm, payload, combine, f"Alltoall@{comm.cid}",
                plan=("alltoall", algo), _sig={"algo": algo})
    if alloc:
        return clone_like(src, mine)
    write_flat(recvbuf, mine, count * size)
    return recvbuf


def Alltoallv(*args) -> Any:
    """``Alltoallv(send, recv, scounts, rcounts, comm)`` mutating | allocating
    ``Alltoallv(send, scounts, rcounts, comm)`` (src/collective.jl:545-578)."""
    if len(args) == 5:
        sendbuf, recvbuf, scounts, rcounts, comm = args
        alloc = False
    elif len(args) == 4:
        sendbuf, scounts, rcounts, comm = args
        recvbuf, alloc = None, True
    else:
        raise TypeError("Alltoallv(send, [recv,] scounts, rcounts, comm)")
    rank, size = comm.rank(), comm.size()
    scounts = [int(c) for c in scounts]
    rcounts = [int(c) for c in rcounts]
    assert_minlength(sendbuf, sum(scounts))
    if not alloc:
        assert_minlength(recvbuf, sum(rcounts))   # before the rendezvous
    payload = (to_wire(sendbuf, sum(scounts)), scounts)

    def combine(cs):
        outs = []
        for r in range(len(cs)):
            parts = []
            for s in range(len(cs)):
                data, sc = cs[s]
                d = int(np.sum(sc[:r]))
                parts.append(data.reshape(-1)[d:d + sc[r]])
            outs.append(_concat(parts, cs[r][0]))
        return outs

    # per-rank send totals differ, so the size-blind (None) decision keeps
    # the selection rank-uniform; pairwise is gated on dtype alone
    dt = getattr(payload[0], "dtype", None)
    numeric = dt is not None and dt != object
    algo = _maybe_explore(comm, "alltoallv", None,
                          _coll_select(comm, "alltoallv", None,
                                       numeric=numeric),
                          numeric=numeric)
    # per-peer counts ride the event IR so the trace verifier can check
    # rank i's scounts[j] against rank j's rcounts[i] (T202 family)
    mine = _run(comm, payload, combine, f"Alltoallv@{comm.cid}",
                plan=("alltoallv", algo),
                _sig={"algo": algo, "scounts": list(scounts),
                      "rcounts": list(rcounts)})
    if alloc:
        return clone_like(sendbuf, mine)
    write_flat(recvbuf, mine, sum(rcounts))
    return recvbuf


# ---------------------------------------------------------------------------
# Reduce / Allreduce / Scan / Exscan / Reduce_scatter
# ---------------------------------------------------------------------------

def _parse_reduce_args(args, has_root: bool, name: str):
    """Shared arg parsing: (send, [recv, [count,]] op, [root,] comm)."""
    tail = 2 if has_root else 1
    n = len(args)
    comm = args[-1]
    root = int(args[-2]) if has_root else None
    op = args[-(tail + 1)]
    head = args[:n - tail - 1]
    if len(head) == 1:
        sendbuf, recvbuf, count = head[0], None, None
        alloc = not isinstance(sendbuf, _InPlace)
    elif len(head) == 2:
        sendbuf, recvbuf, count = head[0], head[1], None
        alloc = False
    elif len(head) == 3:
        sendbuf, recvbuf, count = head
        count = int(count)
        alloc = False
    else:
        raise TypeError(f"{name}(send, [recv, [count,]] op, "
                        + ("root, comm)" if has_root else "comm)"))
    return sendbuf, recvbuf, count, as_op(op), root, comm, alloc


def _reduce_plan(comm: Comm, name: str, mode: str, op: Op, count: int,
                 payload: Any) -> CollectivePlan:
    """The pre-resolved plan for one reduce-family signature (the overlap
    engine's persistent-plan piece): opname tag, combine closure, trace
    signature, multi-process algorithm hint and chunk schedule are built
    once per (comm, flavor, op, count, dtype, array kind) and reused by
    every later same-shape call — the training-loop case pays dict lookups
    instead of closure/format/config work per collective."""
    from . import config
    dtype = getattr(payload, "dtype", None)
    key = (comm.cid, name, mode, op, int(count), str(dtype),
           type(payload).__name__)
    plan = _plans.get(key)
    if plan is not None:
        return plan
    itemsize = getattr(dtype, "itemsize", 0)
    schedule = (ChunkSchedule.maybe(count, itemsize)
                if mode == "reduce" else None)

    def combine(cs, rt=None):
        n = len(cs)
        if mode == "reduce":
            total = _reduce_arrays(cs, op, schedule=schedule)
            if rt is None:              # Allreduce: everyone needs it
                return [total] * n
            # rooted Reduce: ship the combined payload to root only — star
            # egress drops from P×payload to ~zero (VERDICT r2 weak #6;
            # src/collective.jl:605-666 root-only recvbuf)
            return [total if r == rt else None for r in range(n)]
        if mode == "scan":
            return _scan_arrays(cs, op)
        if mode == "exscan":
            # exscan[i] = scan over ranks 0..i-1; rank 0's slot is undefined.
            return [None, *_scan_arrays(cs[:-1], op)]
        raise AssertionError(mode)

    # The multi-process tier picks its algorithm (star / shm / recursive
    # doubling / Rabenseifner / ring / binomial) from the portfolio once
    # per signature; order-sensitive modes (Scan/Exscan) stay on the
    # monolithic star. The selection is cached inside this plan and
    # invalidated with it on config reloads.
    if mode == "reduce":
        from .operators import is_elementwise
        numeric = dtype is not None and str(dtype) != "object"
        nbytes = int(count) * itemsize if numeric and itemsize else None
        coll = "reduce" if name == "Reduce" else "allreduce"
        algo = _coll_select(comm, coll, nbytes,
                            commutative=bool(op.commutative),
                            elementwise=is_elementwise(op), numeric=numeric)
        hint = (coll, op, algo)
    else:
        algo, hint = "star", None
    sig = {"count": int(count), "dtype": str(dtype), "algo": algo}
    plan = CollectivePlan(f"{name}@{comm.cid}", op, combine, sig, hint,
                          schedule, config.GENERATION, algo=algo)
    _plans.put(key, plan)
    return plan


def _explore_reduce_variant(comm: Comm, cplan: CollectivePlan, op: Op,
                            count: int, payload: Any) -> CollectivePlan:
    """Online-tuning hook for the reduce family: plan-cache hits skip
    ``_coll_select`` entirely, so with the bandit live we re-run the
    decision through :func:`_maybe_explore` per call and — only on the
    exploration slots — hand back a shallow variant of the cached plan
    with the algorithm rebound. The variant shares the combine closure and
    chunk schedule; the cached plan itself is never mutated, so steady
    traffic keeps its zero-overhead path."""
    from .operators import is_elementwise
    coll, hop, _ = cplan.hint
    dtype = getattr(payload, "dtype", None)
    itemsize = getattr(dtype, "itemsize", 0)
    numeric = dtype is not None and str(dtype) != "object"
    nbytes = int(count) * itemsize if numeric and itemsize else None
    algo = _maybe_explore(comm, coll, nbytes, cplan.algo,
                          commutative=bool(op.commutative),
                          elementwise=is_elementwise(op), numeric=numeric)
    if algo == cplan.algo:
        return cplan
    return CollectivePlan(cplan.opname, cplan.op, cplan.combine,
                          dict(cplan.sig, algo=algo), (coll, hop, algo),
                          cplan.schedule, cplan.generation, algo=algo)


def _auto_arm_gate(comm, args, sendbuf, recvbuf, op, count, payload, alloc):
    """ISSUE-11 tentpole (a): promote a repeated plain ``Allreduce``
    signature onto the registered persistent path with zero API change.

    Returns ``(runner, model)``. ``runner`` — when the signature's
    consecutive-identical-call streak has crossed
    ``config.auto_arm_threshold`` and a :class:`PlanRegistration` bound —
    executes the whole armed round (rendezvous + copy-out) and the caller
    returns its value directly; ``None`` means take the generic path.
    ``model`` is non-None only under tracing with ``auto_arm_donate`` opted
    in: traced runs always DEMOTE to the fully-evented legacy lane (bitwise
    identical by construction), but the donation window the untraced run
    would have had is modeled with synthetic Start/Wait events so the R302
    pass can still flag a stale aliased result being fed back in — the
    caller invokes ``model(out)`` with the allocating flavor's result.

    Demotion is loud-free and total: trace arming, outstanding nonblocking
    traffic, buffer-identity churn, shape/dtype churn on the lane
    (``PlanCache.auto_note``), ``Comm.free`` (``plans.invalidate``), and
    config reloads (generation check below) all push the signature back to
    the generic star. Without ``auto_arm_donate`` the armed lane runs the
    copy-out contract (``_register_allreduce(donate=False)``), so no user-
    visible aliasing exists for R302 to worry about."""
    from . import config
    from ._runtime import current_env
    cfg = config.load()
    if not (cfg.auto_arm and cfg.registered_buffers):
        return None, None
    env = current_env()
    if env is None:
        return None, None
    ctx, world_rank = env
    cid = comm.cid
    # per-rank key: the thread tier shares ONE PlanCache across rank
    # threads, and each rank's streak/arming is its own
    key = (cid, comm.rank(), "Allreduce", op, int(count),
           str(getattr(payload, "dtype", None)), type(payload).__name__)
    e = _plans.auto_note(key, sendbuf, recvbuf)
    if e is None:
        return None, None
    threshold = max(int(cfg.auto_arm_threshold), 1)

    if _ev.enabled():
        if e.armed:
            _plans.auto_demote(e)
        if not (cfg.auto_arm_donate and alloc and e.streak >= threshold):
            return None, None
        # model the donated-result ring the untraced run would alias:
        # round k's Start re-donates the slot under round k-2's result
        rnd = e.rounds
        e.rounds += 1
        inval = None
        for r, res in e.results:
            if r == rnd - 2:
                inval = _ev.buf_id(res)
        _ev.record_start(comm, "pallreduce", id(e), rnd, invalidates=inval)

        def model(out):
            e.results.append((rnd, out))
            _ev.record_wait(comm, "pallreduce", id(e), rnd, result=out)
        return None, model

    st = _nb_state(ctx, cid, world_rank, create=False)
    if st is not None and st.outstanding:
        # in-flight I* ops own the initiation order; stay generic (the
        # generic path runs through the worker) and drop the armed round
        if e.armed:
            _plans.auto_demote(e)
        return None, None

    reg = e.reg
    if reg is not None and (reg.released or reg.generation
                            != config.GENERATION):
        _plans.auto_demote(e)
        reg = None
    if reg is None:
        if e.streak < threshold or e.ineligible_gen == config.GENERATION:
            return None, None
        reg = _register_allreduce(comm, args, donate=cfg.auto_arm_donate)
        if reg is None or not reg.knob_on:
            if reg is not None:
                _registry.discard(reg)
            e.ineligible_gen = config.GENERATION
            return None, None
        _plans.auto_bind(e, reg)

    # publish the front door: the NEXT identical call dispatches from
    # Allreduce() itself on one dict probe + identity compares, skipping
    # argument parsing and this key construction (_auto_hot_run)
    _plans.auto_hot_set((cid, key[1]),
                        (args, e, sendbuf,
                         getattr(sendbuf, "nbytes", None)))

    def runner():
        _plans.auto_hit(e)
        # flush this thread's stacked fast-armed persistent rounds first
        # so initiation order stays program order; the outstanding-work
        # check _ordered_run would redo just happened above
        if not getattr(_nb_worker_tls, "active", False):
            _demote_fast_armed(cid)
        return reg.run_round()
    return runner, None


_AUTO_MISS = object()


def _auto_hot_run(args: tuple) -> Any:
    """ISSUE-11 front door: dispatch a repeat of an already-armed plain
    ``Allreduce`` straight to its registered round on one dict probe plus
    per-element identity compares against the exact argument tuple that
    armed — skipping argument parsing and signature-key construction, the
    two per-call costs that kept the auto-armed lane measurably over the
    hand-armed Start/Wait figure. Any mismatch — different argument
    objects, tracing armed, a released or stale-generation registration,
    outstanding nonblocking traffic, an in-place resize of the send
    operand — returns ``_AUTO_MISS`` and the call falls through to
    :func:`_reduce_family`, whose full gate owns every demotion edge."""
    comm = args[-1]
    if not isinstance(comm, Comm):
        return _AUTO_MISS
    try:
        lane = (comm.cid, comm.rank())
    except Exception:
        return _AUTO_MISS               # not Init'd etc.: legacy error path
    rec = _plans.auto_hot_get(lane)
    if rec is None:
        return _AUTO_MISS
    pargs, e, send, nbytes = rec
    if len(pargs) != len(args):
        return _AUTO_MISS
    for a, b in zip(pargs, args):
        if a is not b:
            return _AUTO_MISS
    from . import config
    reg = e.reg
    if reg is None or reg.generation != config.GENERATION \
            or getattr(send, "nbytes", None) != nbytes \
            or not reg.armable():
        return _AUTO_MISS
    # stats stay truthful without the table lock: every field touched here
    # is owned by this rank's thread (the signature key is per-(cid, rank))
    # except the aggregate hit counter, which tolerates a lost update
    e.calls += 1
    e.streak += 1
    e.hits += 1
    _plans.auto_hits += 1
    # same program-order rule as the gate's runner: stacked fast-armed
    # persistent rounds on this thread initiate first
    if not getattr(_nb_worker_tls, "active", False):
        _demote_fast_armed(lane[0])
    return reg.run_round()


def _reduce_family(args, has_root: bool, mode: str, name: str) -> Any:
    """One reduce-family call inside ONE pvar op scope, open from the entry
    (this one's, or ``Allreduce``'s, which then owns it) to the return:
    argument parsing, the plan and the auto-arm gate are its front door, and
    the copy-out into the user's recvbuf lands in the same phase breakdown
    as the channel's rendezvous/fold spans (the inner ``_run`` sees the open
    scope and defers finalization). The body leaves what the op was in
    ``sc.meta``; whoever opened the scope closes it."""
    own = _pv.op_begin() if (_pv.enabled() or _ev.enabled()) else None
    sc = own or _pv.scope()
    try:
        sendbuf, recvbuf, count, op, root, comm, alloc = _parse_reduce_args(args, has_root, name)
        rank, size = comm.rank(), comm.size()
        scalar_in = np.isscalar(sendbuf) or isinstance(sendbuf, (int, float, complex, bool, np.generic))
        inplace = isinstance(sendbuf, _InPlace)
        if inplace:
            if _is_none(recvbuf):
                raise MPIError(f"IN_PLACE {name} needs a buffer")
            sendbuf = recvbuf
        if count is None:
            count = element_count(sendbuf)
        assert_minlength(sendbuf, count)
        if recvbuf is not None and not _is_none(recvbuf) and not inplace:
            assert_minlength(recvbuf, count)
        if mode == "reduce":
            # Zero-copy contribution: the reduce fold's distributed output is
            # always FRESH data (for n >= 2 the fold allocates; for n == 1 every
            # consumer below copies or self-assigns), and every rank is blocked
            # in the rendezvous until the fold has run — so the live buffer is
            # safe to expose and the to_wire snapshot copy is pure overhead.
            # Scan/Exscan keep the snapshot: Exscan hands rank 0's contribution
            # to rank 1 AS-IS, aliasing rank 0's buffer after it returns.
            payload = wire_view(sendbuf, count)
        else:
            payload = to_wire(sendbuf, count)

        # auto-arm (ISSUE 11): a repeated same-signature plain Allreduce is
        # promoted onto the registered persistent path; the armed runner skips
        # plan lookup AND bandit exploration (auto-armed plans never explore —
        # the explored variant would fork the call off its registered opname
        # lockstep). Under tracing the gate only returns a trace model.
        _model = None
        if mode == "reduce" and not has_root and name == "Allreduce" \
                and not scalar_in:
            _runner, _model = _auto_arm_gate(comm, args, sendbuf, recvbuf, op,
                                             count, payload, alloc)
            if _runner is not None:
                return _runner()

        cplan = _reduce_plan(comm, name, mode, op, count, payload)
        if mode == "reduce" and _tune_online.state() is not None:
            cplan = _explore_reduce_variant(comm, cplan, op, count, payload)
        if sc is not None:
            sc.meta = (name.lower(), cplan.sig.get("algo"),
                       cplan.sig.get("dtype"), _pv.payload_nbytes(payload))
        # while tracing, stamp the contribution buffer's identity into the
        # signature (copy — cplan.sig may be plan-cache shared) so the R302
        # pass can see a stale donated result fed back into a reduction
        sig = dict(cplan.sig, bufid=_ev.buf_id(sendbuf)) if _ev.enabled() \
            else cplan.sig
        if has_root:
            result = _run_rooted(comm, root, payload, cplan.combine,
                                 cplan.opname, plan=cplan.hint, _sig=sig)
        else:
            result = _run(comm, payload, cplan.combine, cplan.opname,
                          plan=cplan.hint, _sig=sig)
        i_get_result = (not has_root) or rank == root
        if mode == "exscan" and result is None:
            # rank 0's Exscan output is undefined (src/collective.jl:834-855);
            # leave buffers untouched, return the input unchanged.
            if alloc:
                return sendbuf if scalar_in else clone_like(sendbuf, np.asarray(sendbuf))
            return recvbuf if not inplace else sendbuf
        if not i_get_result:
            return None if alloc else recvbuf
        if alloc:
            if scalar_in:
                out = np.asarray(result)
                return out.item() if out.ndim == 0 or out.size == 1 else out
            shaped = _shape_result(result, sendbuf, count)
            if sc is None:
                out = clone_like(sendbuf, shaped)
            else:
                t0 = _pv.monotonic()
                out = clone_like(sendbuf, shaped)
                sc.spans.append(("copy", t0, _pv.monotonic()))
            if _model is not None:
                _model(out)     # R302 donation-window model (auto-arm)
            return out
        target = sendbuf if inplace else recvbuf
        if sc is None:
            write_flat(target, result, count)
        else:
            t0 = _pv.monotonic()
            # the result lives where the fold ran, the rank's DeviceBuffer on
            # its own chip: a copy-out between the two is counted
            if isinstance(target, DeviceBuffer):
                if target.setflat(result, count):
                    _pv.note_moved(sc, False, result.nbytes)
            else:
                write_flat(target, result, count)
            sc.spans.append(("copy", t0, _pv.monotonic()))
            _watch_copyout(sc, t0, target)
        return target
    finally:
        if own is not None:
            _pv.op_end(own, args[-1] if args else None)


def _watch_copyout(sc, t0: float, tgt: Any, across: bool = False) -> None:
    """The device's end of a copy-out between chips (``perfvars.watch``),
    or, ``across``, of a result that the fold over the ranks' chips left on
    this rank's own: when it was there."""
    if sc.tree and (across or sc.moved_out is not None):
        _pv.watch(sc, t0, ("copy_out.done", getattr(tgt, "value", tgt)))


def _shape_result(result: Any, like: Any, count: int) -> Any:
    arr = extract_array(like)
    if arr is None or getattr(result, "shape", None) == arr.shape:
        return result   # metadata-only check; no dispatch on the hot lane
    if arr.size == count and np.asarray(result).size == count:
        return np.asarray(result).reshape(arr.shape) if not type(result).__module__.startswith("jax") \
            else result.reshape(arr.shape)
    return result


def Reduce(*args) -> Any:
    """``Reduce(send, recv, [count,] op, root, comm)`` | ``Reduce(IN_PLACE,
    buf, op, root, comm)`` | allocating ``Reduce(send, op, root, comm)``
    (src/collective.jl:605-666). Result lands on root only."""
    return _reduce_family(args, has_root=True, mode="reduce", name="Reduce")


def Allreduce(*args) -> Any:
    """``Allreduce(send, recv, [count,] op, comm)`` | ``Allreduce(IN_PLACE,
    buf, op, comm)`` | allocating ``Allreduce(send, op, comm)``
    (src/collective.jl:691-738). Deterministic rank-ordered reduction. A
    repeated identical call auto-arms onto the registered persistent path
    (ISSUE-11) and repeat hits dispatch through the front door below."""
    # the pvar op scope opens HERE, so the front door of either lane is in
    # it; a nested call (the nonblocking worker inside an outer op) gets
    # None and the outer owner's scope collects its phases
    sc = _pv.op_begin() if (_pv.enabled() or _ev.enabled()) else None
    try:
        if len(args) >= 3:
            out = _auto_hot_run(args)
            if out is not _AUTO_MISS:
                return out
        return _reduce_family(args, has_root=False, mode="reduce",
                              name="Allreduce")
    finally:
        if sc is not None:
            _pv.op_end(sc, args[-1] if args else None)


def Scan(*args) -> Any:
    """Inclusive prefix reduction over ranks (src/collective.jl:760-808)."""
    return _reduce_family(args, has_root=False, mode="scan", name="Scan")


def Exscan(*args) -> Any:
    """Exclusive prefix reduction; rank 0's result undefined
    (src/collective.jl:834-882)."""
    return _reduce_family(args, has_root=False, mode="exscan", name="Exscan")


def Reduce_scatter(sendbuf: Any, recvbuf: Any, counts: Sequence[int], op: Any,
                   comm: Comm) -> Any:
    """Reduce then scatter by counts — absent from the reference (SURVEY.md
    §2.3: trivially composable / native in XLA as psum_scatter); provided
    natively here."""
    rank, size = comm.rank(), comm.size()
    op = as_op(op)
    counts = [int(c) for c in counts]
    total = sum(counts)
    assert_minlength(sendbuf, total)
    payload = (to_wire(sendbuf, total), counts)

    def combine(cs):
        # Reduce_scatter has no root: every rank's counts must agree.
        lists = [c[1] for c in cs]
        if any(l != lists[0] for l in lists[1:]):
            raise MPIError(f"Reduce_scatter counts differ across ranks: {lists}",
                           code=_ec.ERR_COUNT)
        red = _reduce_arrays([c[0] for c in cs], op)
        displs = np.concatenate([[0], np.cumsum(lists[0])])
        return [red.reshape(-1)[displs[r]:displs[r] + lists[0][r]]
                for r in range(len(cs))]

    mine = _run(comm, payload, combine, f"Reduce_scatter@{comm.cid}")
    if recvbuf is None:
        return clone_like(sendbuf, mine)
    assert_minlength(recvbuf, counts[rank])
    write_flat(recvbuf, mine, counts[rank])
    return recvbuf


def Reduce_scatter_block(sendbuf: Any, recvbuf: Any, op: Any, comm: Comm) -> Any:
    """Equal-block Reduce_scatter (recvcount = sendcount / comm size)."""
    size = comm.size()
    n = element_count(sendbuf)
    if n % size != 0:
        raise MPIError(f"send count {n} not divisible by comm size {size}",
                       code=_ec.ERR_COUNT)
    return Reduce_scatter(sendbuf, recvbuf, [n // size] * size, op, comm)


# ---------------------------------------------------------------------------
# Nonblocking collectives (MPI-3 Ibarrier/Ibcast/Iallreduce/… — absent from
# the reference v0.14.2, SURVEY.md §2.3 note; provided natively, beyond
# parity). Each communicator gets a per-rank single-thread worker, so this
# rank's collectives INITIATE on the rendezvous in program order (the MPI
# ordering contract) while the caller overlaps compute or P2P. Completion
# integrates with the whole Wait/Test family via a Request subclass.
# ---------------------------------------------------------------------------

class CollRequest:
    """Request handle for a nonblocking collective.

    Duck-types the :class:`tpu_mpi.pointtopoint.Request` completion
    protocol (``test``/``wait``/``active``/``cancel``), so Wait/Test/
    Waitall/Testall/Waitany/Testany/Waitsome/Testsome accept mixed lists
    of P2P and collective requests. ``result`` carries the allocating
    variant's return value after completion; errors raised inside the
    collective (mismatch, abort, deadlock) re-raise on Wait/Test.

    MPI contract (caller's side): do not touch the operation's buffers
    between initiation and completion, and initiate collectives on a
    communicator in the same order on every rank.
    """

    def __init__(self, future):
        self._future = future
        self.result = None
        self.status = None
        self._done = False
        self._inactive = False
        self.kind = "coll"
        self.buffer = None
        self.comm_cid = None     # pvar wait attribution (set by _nb_submit)
        # in-flight chunk state (overlap engine) — set by _nb_submit, advanced
        # by the progress worker, readable any time from the caller's thread
        self.progress = None

    def _complete(self) -> None:
        self.result = self._future.result()   # re-raises collective errors
        from .pointtopoint import STATUS_EMPTY
        self.status = STATUS_EMPTY
        self._done = True

    def test(self) -> bool:
        if self._done:
            return True
        if not self._future.done():
            return False
        self._complete()
        return True

    def wait(self):
        from .pointtopoint import STATUS_EMPTY
        if self._inactive:
            return self.status or STATUS_EMPTY
        if not self._done:
            # wait_owned(): an outer owner (PersistentCollRequest) already
            # accounts this round's wall clock — adding wait_ns here too
            # would double-count it (the outermost-owner rule, ISSUE-6).
            if _pv.enabled() and not _pv.wait_owned():
                t0 = _pv.monotonic()
                try:
                    self._complete()
                finally:
                    _pv.add_wait(_pv.monotonic() - t0, cid=self.comm_cid)
            else:
                self._complete()
        return self._consume()

    def _consume(self):
        """Surface the completion (Wait/Test-family contract): go inactive
        like a consumed P2P request; ``result`` stays readable."""
        from .pointtopoint import STATUS_EMPTY
        self._inactive = True
        return self.status or STATUS_EMPTY

    @property
    def active(self) -> bool:
        return not self._inactive

    def cancel(self) -> None:
        raise MPIError("nonblocking collectives cannot be cancelled")

    def __repr__(self) -> str:
        return f"<CollRequest done={self._done}>"


class _NbState:
    """Per-(comm, rank) nonblocking-collective worker: a single thread, so
    this rank's collectives INITIATE on the rendezvous in submission order,
    plus an outstanding counter that lets blocking collectives detect
    in-flight nonblocking ones and route through the same worker (ordering
    would otherwise race — an MPI-legal ``Ibarrier; Bcast; Wait`` could
    initiate in different orders on different ranks)."""

    def __init__(self, world_rank: int):
        from concurrent.futures import ThreadPoolExecutor
        self.executor = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix=f"tpu-mpi-nbcoll-{world_rank}")
        self.outstanding = 0
        self.lock = threading.Lock()
        # submission id -> op name, insertion-ordered: names the in-flight
        # ops for diagnostics (Comm.free on a busy comm, lease reclamation)
        self._seq = 0
        self._pending: dict[int, str] = {}

    def submit(self, fn, opname: str = "collective"):
        with self.lock:
            self.outstanding += 1
            self._seq += 1
            sid = self._seq
            self._pending[sid] = (opname, None)
        fut = self.executor.submit(fn)
        with self.lock:
            if sid in self._pending:        # done() may already have pruned
                self._pending[sid] = (opname, fut)

        def done(_):
            with self.lock:
                self.outstanding -= 1
                self._pending.pop(sid, None)

        fut.add_done_callback(done)
        return fut

    def pending_ops(self) -> list:
        """Names of the submissions not yet completed, oldest first. A
        future can complete (its waiter unblocks) a beat before its done
        callback prunes the table, so consult the future itself — a
        ``Wait(); free()`` sequence must never see a phantom pending op."""
        with self.lock:
            return [name for name, fut in self._pending.values()
                    if fut is None or not fut.done()]

    def shutdown(self) -> None:
        self.executor.shutdown(wait=False)


_nb_worker_tls = threading.local()    # True on a collective worker thread


def _nb_state(ctx, cid, world_rank, create: bool):
    key = ("nbcoll", cid, world_rank)
    with ctx.objects_lock:
        st = ctx.objects.get(key)
        if st is None and create:
            st = _NbState(world_rank)
            ctx.objects[key] = st
        return st


def nb_pending(ctx, cid, world_rank) -> list:
    """Names of this rank's in-flight nonblocking collectives on one comm
    (empty when the worker is idle or was never created). Consulted by
    ``Comm.free`` so freeing under in-flight ops is a typed error naming
    the offenders instead of a strict-mode-only leak assert."""
    st = _nb_state(ctx, cid, world_rank, create=False)
    return st.pending_ops() if st is not None else []


def nb_shutdown(ctx, cid=None, world_rank=None) -> None:
    """Release nonblocking-collective workers: the ones of one comm+rank
    (Comm.free) or every one owned by a rank (Finalize)."""
    with ctx.objects_lock:
        keys = [k for k in ctx.objects
                if isinstance(k, tuple) and k and k[0] == "nbcoll"
                and (cid is None or k[1] == cid)
                and (world_rank is None or k[2] == world_rank)]
        states = [ctx.objects.pop(k) for k in keys]
    for st in states:
        st.shutdown()


def _nb_submit(comm: Comm, fn, opname: str = "collective") -> CollRequest:
    """Run ``fn`` on this rank's per-comm collective worker (the host-path
    progress engine: the worker thread advances the collective — including
    its pipeline chunks — while the caller is in user code; the request's
    ``progress`` exposes the in-flight chunk state)."""
    from ._runtime import require_env, set_env
    from .overlap import ChunkProgress, bind_progress, demote_fast_armed

    # a fast-armed persistent round on this comm has not rendezvoused yet:
    # it must initiate (on the worker) BEFORE this submission to keep the
    # per-comm initiation order equal to program order
    demote_fast_armed(comm.cid)
    ctx, world_rank = require_env()
    st = _nb_state(ctx, comm.cid, world_rank, create=True)
    prog = ChunkProgress()

    def run():
        # the worker impersonates the initiating rank (thread-tier ranks
        # are TLS-bound; the proc tier's process-global binding also works)
        set_env((ctx, world_rank))
        _nb_worker_tls.active = True
        bind_progress(prog)
        prog.stage = "running"
        try:
            return fn()
        finally:
            prog.stage = "done"
            bind_progress(None)
            _nb_worker_tls.active = False
            set_env(None)

    req = CollRequest(st.submit(run, opname=opname))
    req.progress = prog
    req.comm_cid = comm.cid       # attributes the caller's Wait time (pvars)
    return req


def _ordered_run(comm: Comm, call):
    """Initiation-order guard for BLOCKING collectives: when this rank's
    nonblocking worker has outstanding work on this comm, run the blocking
    collective THROUGH the worker (submission order = program order) and
    wait; otherwise call directly. Without this, an MPI-legal
    ``Ibarrier(comm); Bcast(buf, 0, comm); Wait(req)`` could initiate in
    different orders on different ranks and mispair rendezvous rounds."""
    if getattr(_nb_worker_tls, "active", False):
        return call()                      # already ON the worker
    # fast-armed persistent rounds initiate before this blocking collective
    # (same program-order rule as the worker submissions)
    from .overlap import demote_fast_armed
    demote_fast_armed(comm.cid)
    from ._runtime import current_env
    env = current_env()
    if env is None:
        return call()
    ctx, world_rank = env
    st = _nb_state(ctx, comm.cid, world_rank, create=False)
    if st is None or st.outstanding == 0:
        # an idle worker has fully completed everything it initiated, so a
        # direct call cannot overtake anything (and a CONCURRENT submitter
        # from another user thread is the user's ordering responsibility,
        # exactly as in MPI THREAD_MULTIPLE)
        return call()
    from ._runtime import set_env

    def run():
        set_env((ctx, world_rank))
        _nb_worker_tls.active = True
        try:
            return call()
        finally:
            _nb_worker_tls.active = False
            set_env(None)

    return st.submit(run).result()


def Ibarrier(comm: Comm) -> CollRequest:
    """Nonblocking barrier: complete once every rank has entered."""
    return _nb_submit(comm, lambda: Barrier(comm), opname="Ibarrier")


def Ibcast(buf: Any, root: int, comm: Comm) -> CollRequest:
    """Nonblocking Bcast; ``req.result`` is the (mutated) buffer."""
    return _nb_submit(comm, lambda: Bcast(buf, root, comm), opname="Ibcast")


def Iallreduce(*args) -> CollRequest:
    """Nonblocking Allreduce (same flavors as :func:`Allreduce`); the
    allocating variant's value arrives in ``req.result``."""
    return _nb_submit(_comm_of(args), lambda: Allreduce(*args),
                      opname="Iallreduce")


def Ireduce(*args) -> CollRequest:
    """Nonblocking rooted Reduce."""
    return _nb_submit(_comm_of(args), lambda: Reduce(*args), opname="Ireduce")


def Igather(*args) -> CollRequest:
    """Nonblocking rooted Gather."""
    return _nb_submit(_comm_of(args), lambda: Gather(*args), opname="Igather")


def Iallgather(*args) -> CollRequest:
    """Nonblocking Allgather."""
    return _nb_submit(_comm_of(args), lambda: Allgather(*args),
                      opname="Iallgather")


def Iscatter(*args) -> CollRequest:
    """Nonblocking rooted Scatter."""
    return _nb_submit(_comm_of(args), lambda: Scatter(*args), opname="Iscatter")


def Ialltoall(*args) -> CollRequest:
    """Nonblocking Alltoall."""
    return _nb_submit(_comm_of(args), lambda: Alltoall(*args),
                      opname="Ialltoall")


def Iscan(*args) -> CollRequest:
    """Nonblocking inclusive Scan."""
    return _nb_submit(_comm_of(args), lambda: Scan(*args), opname="Iscan")


def Iexscan(*args) -> CollRequest:
    """Nonblocking exclusive Scan."""
    return _nb_submit(_comm_of(args), lambda: Exscan(*args), opname="Iexscan")


def _comm_of(args) -> Comm:
    if not args or not isinstance(args[-1], Comm):
        raise TypeError("the last argument must be the communicator")
    return args[-1]


# ---------------------------------------------------------------------------
# Persistent collectives (MPI-4 MPI_Allreduce_init family), mirroring the
# persistent P2P machinery (pointtopoint.Send_init/Recv_init + Prequest):
# the arguments bind once, every Start initiates one round on the progress
# worker, and the first round populates the plan cache so later rounds skip
# per-call setup entirely — the training-loop shape.
# ---------------------------------------------------------------------------

def _registered_device_fold(op: Op, count: int, dtype: Any, size: int,
                            device: Any, donate: bool = True):
    """The donated-accumulator fold executable for the registered device
    lane: ONE XLA computation compiled AOT at plan creation for ``device``
    (comm rank 0's chip — where :func:`_colocated` gathers a fold) with
    ``donate_argnums`` on the accumulator, so every round's rank-ordered
    chain reuses the accumulator's device buffer in place instead of
    allocating a fresh output (the per-round HBM alloc + copy the generic
    ``_jitted_fold`` pays). Two pre-pinned accumulator slots alternate
    (``ring``): donation consumes a slot, so round k's result stays valid
    until round k+2's fold re-donates that slot — the persistent in-place
    contract documented in docs/performance.md. Operands living on other
    chips are copied to ``device`` each round; every rank's copy-out moves
    the result to its own chip: a star. It is what ranks that share a chip
    register (one chip; more ranks than chips, where ``device_for`` wraps),
    and a rank whose operand is not on its own chip; ranks with a chip each
    register :func:`_exchange_combine` instead, chosen once, at plan
    creation, and not per round. Returns the combine closure, or None when
    the op can't trace (the caller then declines the device registration
    and the generic path applies); a fold that traces and then fails to
    compile raises."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    count = int(count)
    dt = np.dtype(dtype)
    home = SingleDeviceSharding(device)
    sds = jax.ShapeDtypeStruct((count,), dt, sharding=home)

    plain_fold = _left_chain(op)

    def chain(acc, *xs):
        # the .set() seeds the donated buffer; the fold is then the same
        # rank-ordered left chain as _jitted_fold — bitwise-identical
        return plain_fold(acc.at[:].set(xs[0]), *xs[1:])

    if not _traceable(plain_fold, *([sds] * size)):
        return None                 # host-only / untraceable op: no lane
    with _pv.setup_span("fold.compile", function="plain_fold"):
        plain = jax.jit(plain_fold).lower(*([sds] * size)).compile()
    if donate:
        with _pv.setup_span("fold.compile", function="chain"):
            donated = jax.jit(chain, donate_argnums=(0,)) \
                .lower(sds, *([sds] * size)).compile()
        ring = [jnp.zeros((count,), dt, device=home),
                jnp.zeros((count,), dt, device=home)]
    state = {"k": 0}

    def combine(cs, rt=None):
        k = state["k"]
        state["k"] = k + 1
        n = len(cs)
        good = n == size and all(
            is_jax_array(c) and tuple(c.shape) == (count,) and c.dtype == dt
            for c in cs)
        if good:
            sc = _pv.scope()
            t0 = _pv.monotonic() if sc is not None and sc.tree else None
            cs = _colocate(cs, home)
            slot = ring[k & 1] if donate else None
            # copy-out contract (auto-armed lane, ``donate=False``): the
            # AOT chain still skips per-round trace/lower work, but every
            # round's output is a fresh array — no slot is ever re-donated
            # under a result the user may still hold (the R302 hazard).
            # Likewise an operand aliasing the accumulator (a rank fed a
            # previous result straight back) can't be donated over
            if slot is not None and not any(c is slot for c in cs):
                out = ring[k & 1] = donated(slot, *cs)
            else:
                out = plain(*cs)
            # a donated slot may be donated again (round k + 2) before the
            # watcher reaches it: that round then goes unstamped
            _watch_fold(cs, out, t0)
            return [out] * n
        # a peer contributed a host / reshaped payload this round: generic
        total = _reduce_arrays(list(cs), op)
        return [total] * n

    return combine


def _exchange_combine(op: Op, count: int, dtype: Any,
                      devices: Sequence[Any]):
    """The combine of the registered device lane where every rank sits on
    a chip of its own (``devices``, in rank order): the rank-ordered left
    fold as the ONE executable over those chips (:func:`_exchange_fold`),
    dispatched once by the round's last arriver. Each contribution, already
    on its rank's chip, is one shard of a global array (no copy); every
    rank gets its own shard of the output back, on its own chip, so its
    copy-out rebinds (``DeviceBuffer.setflat``'s fast path) and no
    ``device_put`` follows. Nothing is donated: every round's output is
    fresh, which keeps a persistent handle's result valid for longer than
    its contract asks.

    A round in which some rank contributed anything but a jax array of the
    registered count and dtype on that rank's chip takes the generic
    ``_reduce_arrays`` fold, star and all. Returns None when the op can't
    trace, as :func:`_registered_device_fold` does."""
    import jax
    from jax.sharding import SingleDeviceSharding
    count = int(count)
    dt = np.dtype(dtype)
    size = len(devices)
    homes = [SingleDeviceSharding(d) for d in devices]

    if not _traceable(_left_chain(op),
                      *([jax.ShapeDtypeStruct((count,), dt)] * size)):
        return None
    run, sharding = _exchange_fold(op, count, dt, devices)
    crossed = 2 * (size - 1) * count * dt.itemsize

    def combine(cs, rt=None):
        if len(cs) != size or not all(
                is_jax_array(c) and c.shape == (count,) and c.dtype == dt
                and c.sharding == h for c, h in zip(cs, homes)):
            return [_reduce_arrays(list(cs), op)] * len(cs)
        sc = _pv.scope()
        t0 = _pv.monotonic() if sc is not None and sc.tree else 0.0
        whole = jax.make_array_from_single_device_arrays(
            (size * count,), sharding, cs)
        with _exchange_launch:
            out = run(whole)
        if sc is not None:
            _pv.note_exchanged(sc, crossed)
            if sc.tree:         # ``copy_in.done``: the operands were ready
                _pv.watch(sc, t0, ("copy_in.done", cs), ("fold.done", out))
        mine = {s.device: s.data for s in out.addressable_shards}
        return [mine[d] for d in devices]

    return combine


def _across_chips(devices: Sequence[Any], rank: int, mine: Any) -> bool:
    """Whether ``rank`` registers the fold over the ranks' chips: every rank
    of ``devices`` (in rank order) has one of its own, and this rank's
    operand ``mine`` lives on its (a rank that keeps its data elsewhere
    could never take part in a round of it). A complex operand has no
    integer of its width to cross as where the compiler sums to move (see
    :func:`_exchange_fold`): it keeps the star."""
    return (len(devices) > 1 and len(set(devices)) == len(devices)
            and mine.dtype.kind != "c"
            and mine.devices() == {devices[rank]})


def _sums_to_move(hlo: str) -> bool:
    """Whether a compiled exchange (its HLO text) moves operands by adding
    them to zeros somewhere: the only arithmetic the fold itself asks for
    is on the chip, so any reducing collective is the compiler's way of
    moving, and not exact for every float."""
    return "all-reduce" in hlo or "reduce-scatter" in hlo


def _exchange_fold(op: Op, count: int, dt: Any, devices: Sequence[Any]):
    """``(executable, sharding)`` of the rank-ordered fold over ranks that
    each sit on a chip of their own: one ``shard_map`` over a 1-D mesh of
    ``devices`` in rank order, taking the global ``(size * count,)`` array
    whose shard r IS rank r's operand and returning one whose every shard
    is the whole result.

    Inside, for an operator known to act per element: an all-to-all, so
    that chip r holds slice r of every rank's operand (the last slice
    padded in-graph where ``size`` does not divide the count); the same
    left chain (:func:`_left_chain`) over those slices, in rank order;
    an all-gather of the folded slices. An elementwise left fold is separable
    by slice, so this is bit-identical to the star's fold, for floats too,
    where ``psum``, ``psum_scatter`` or a ring sum in another order and are
    a different result. Each chip sends and receives (size-1)/size of a
    payload twice, instead of size-1 payloads in and as many out through
    one chip. For a user operator, which may couple elements: an all-gather
    of the whole operands and the chain on every chip, in-graph and
    bit-identical as well. XLA may build an all-gather from an all-reduce
    over zero padding (on the v5e it does where a slice misses the chip's
    tiling), and ``-0.0 + 0.0`` is ``0.0``: a program in which the compiler
    left such a sum (:func:`_sums_to_move`) is compiled again with floats
    crossing as unsigned integers of their width. Not as the rule: each
    conversion is a copy through HBM there, 10 of 35 ms at 1 GiB.

    Compiled once per signature and process under the ``fold.compile``
    set-up span, whichever rank's registration comes first; the others
    wait for it and share it."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from .operators import is_elementwise
    from .xla import collectives as _xc
    size = len(devices)
    key = (op.fn, count, str(dt), tuple(devices))
    with _fold_lock:
        entry = _exchange_compiled.get(key)
        if entry is None:
            entry = _exchange_compiled[key] = [threading.Lock(), None]
            while len(_exchange_compiled) > _FOLD_CAP:
                _exchange_compiled.popitem(last=False)
    with entry[0]:
        if entry[1] is not None:
            return entry[1]
        fold = _left_chain(op)
        sliced = is_elementwise(op) and count > 0
        each = -(-count // size)        # a slice's elements
        sharding = NamedSharding(Mesh(np.array(devices), ("rank",)),
                                 P("rank"))
        whole = jax.ShapeDtypeStruct((size * count,), dt, sharding=sharding)

        def compiled(as_bits: bool):
            def crossing(move, x):
                if not as_bits:
                    return move(x)
                bits = jax.lax.bitcast_convert_type(
                    x, jnp.dtype(f"uint{8 * x.dtype.itemsize}"))
                return jax.lax.bitcast_convert_type(move(bits), x.dtype)

            def exchange_fold(x):
                if sliced:
                    flat = crossing(lambda v: _xc.alltoall(
                        jnp.pad(v, (0, each * size - count)), axis="rank"), x)
                    xs = [flat[i * each:(i + 1) * each] for i in range(size)]
                else:
                    xs = crossing(lambda v: _xc.allgather(v, axis="rank"), x)
                acc = fold(*xs)
                if sliced:
                    acc = crossing(lambda v: _xc.allgather(
                        v, axis="rank", tiled=True), acc)[:count]
                return acc

            return jax.jit(jax.shard_map(
                exchange_fold, mesh=sharding.mesh, in_specs=P("rank"),
                out_specs=P("rank"))).lower(whole).compile()

        with _pv.setup_span("fold.compile", function="exchange_fold"):
            run = compiled(False)
            if jnp.issubdtype(dt, jnp.floating) \
                    and _sums_to_move(run.as_text()):
                run = compiled(True)
        entry[1] = (run, sharding)
    return entry[1]


def _register_allreduce(comm: Comm, args,
                        donate: bool = True) -> Optional[PlanRegistration]:
    """:func:`_bind_allreduce` under the ``plan.register`` set-up span."""
    with _pv.setup_span("plan.register", cid=str(getattr(comm, "cid", None))):
        return _bind_allreduce(comm, args, donate)


def _bind_allreduce(comm: Comm, args,
                    donate: bool = True) -> Optional[PlanRegistration]:
    """Build the registered-buffer fast path of one ``Allreduce_init``
    signature (the ISSUE-6 tentpole), or None when the operands are not
    eligible (every round then takes the generic worker path).

    ``donate=False`` selects the auto-arm copy-out contract (ISSUE 11):
    the allocating flavor returns a FRESH array every round instead of the
    plan-private registered result, and the device lane compiles only the
    non-donated fold — bitwise identical to the generic path with none of
    the R302 donated-reuse hazard, at the cost of one output copy.
    Hand-armed ``Allreduce_init`` callers keep ``donate=True`` (documented
    persistent in-place result semantics).

    Everything a round needs is resolved and PINNED here, at plan-creation
    time:

    - the send operand's flat wire view (``buffers.pinned_wire_view``) —
      rendezvous ships the pre-bound view, no per-call normalization;
    - the fold accumulator (``buffers.register_scratch``) — the chunked
      in-place ufunc fold lands in plan-private pinned memory (the generic
      ``_chunked_fold`` allocates its output every call);
    - the copy-out target — the user's recv buffer's pinned view, or a
      per-rank registered result array for the allocating flavor
      (returned in place round after round: ``Allreduce_init`` callers opt
      into persistent in-place result semantics, see docs/performance.md);
    - on the device lane (thread tier), the donated fold executable
      (:func:`_registered_device_fold`) compiled once per plan;
    - on the multi-process tier, the same-host shm segment lease
      (``ProcChannel.shm_bind``) so no round pays the lazy mmap.

    The round closure then does ONE rendezvous round trip inline on the
    calling thread — no arg parse, no plan lookup, no worker hop, zero
    steady-state allocation — with the thread tier's channel lock released
    during the fold (``unlocked_fold``: the combine only touches the
    plan-private scratch)."""
    from . import config
    from ._runtime import CollectiveChannel as _ThreadChannel, current_env
    from .buffers import pinned_wire_view, register_scratch

    if not isinstance(comm, Comm) or isinstance(comm, Intercomm):
        return None
    env = current_env()
    if env is None:
        return None                 # outside an SPMD env: legacy path raises
    ctx, world_rank = env
    cfg = config.load()
    if not cfg.registered_buffers:
        # knob off: keep a disabled stub so a later config reload (which
        # bumps GENERATION) re-runs this factory and can bind for real
        def _off():
            raise MPIError("registered fast path is disabled")
        return _registry.add(PlanRegistration(
            comm.cid, config.GENERATION, _off, knob_on=False))
    try:
        sendbuf, recvbuf, count, op, _root, _c, alloc = \
            _parse_reduce_args(args, False, "Allreduce")
    except Exception:
        return None                 # malformed args: legacy path raises
    inplace = isinstance(sendbuf, _InPlace)
    if inplace:
        if _is_none(recvbuf):
            return None
        sendbuf = recvbuf
    try:
        if count is None:
            count = element_count(sendbuf)
        assert_minlength(sendbuf, count)
    except Exception:
        return None
    count = int(count)
    size, rank = comm.size(), comm.rank()
    channel = comm.channel()
    thread_tier = isinstance(channel, _ThreadChannel)
    across = False      # the device lane's ranks have a chip each

    from .operators import is_elementwise
    sendview = pinned_wire_view(sendbuf, count)
    scratch: tuple
    if sendview is not None:
        # ---- host lane: pinned views + registered in-place chunk fold ----
        if op.ufunc is None or not is_elementwise(op):
            return None
        payload = sendview
        acc = register_scratch(count, sendview.dtype)
        contrib = lambda: sendview
        cplan = _reduce_plan(comm, "Allreduce", "reduce", op, count, payload)
        bounds = (tuple(cplan.schedule) if cplan.schedule is not None
                  else ((0, count),))
        shared = [acc] * size

        def combine(cs, rt=None):
            flats = []
            for c in cs:
                if isinstance(c, np.ndarray) and c.dtype == acc.dtype \
                        and c.size == count:
                    flats.append(c.reshape(-1))
                else:
                    # a peer contributed a device / promoted payload this
                    # round: fold generically, land it in the pinned scratch
                    total = _reduce_arrays(list(cs), op,
                                           schedule=cplan.schedule)
                    np.copyto(acc, np.asarray(total).reshape(-1),
                              casting="unsafe")
                    return shared
            for lo, hi in bounds:
                np.copyto(acc[lo:hi], flats[0][lo:hi])
                for f in flats[1:]:
                    op.ufunc(acc[lo:hi], f[lo:hi], out=acc[lo:hi])
            return shared

        if alloc:
            out = register_scratch(count, sendview.dtype)
            shape = np.shape(sendbuf)
            ret = out.reshape(shape) \
                if int(np.prod(shape, dtype=np.int64)) == count else out
            scratch = (acc, out)

            if donate:
                def copyout(res):
                    if res is not out:
                        np.copyto(out, np.asarray(res).reshape(-1),
                                  casting="unsafe")
                    return ret
            else:
                def copyout(res):
                    if res is not out:
                        np.copyto(out, np.asarray(res).reshape(-1),
                                  casting="unsafe")
                    return np.array(ret, copy=True)
        else:
            tgt = sendbuf if inplace else recvbuf
            tgtview = sendview if inplace else pinned_wire_view(tgt, count)
            if tgtview is None:
                return None         # unbindable recv operand: legacy path
            scratch = (acc,)

            def copyout(res):
                resarr = np.asarray(res).reshape(-1)
                if resarr is not tgtview and resarr.base is not tgtview:
                    np.copyto(tgtview, resarr, casting="unsafe")
                return tgt
    elif (isinstance(sendbuf, DeviceBuffer) or is_jax_array(sendbuf)) \
            and thread_tier:
        # ---- device lane: donated-accumulator fold, thread tier only ----
        payload = to_wire(sendbuf, count)
        cplan = _reduce_plan(comm, "Allreduce", "reduce", op, count, payload)
        devices = [ctx.device_for(comm.world_rank_of(r)) for r in range(size)]
        across = _across_chips(devices, rank, payload)
        combine = _exchange_combine(op, count, payload.dtype, devices) \
            if across else _registered_device_fold(
                op, count, payload.dtype, size, devices[0], donate=donate)
        if combine is None:
            return None
        contrib = lambda: to_wire(sendbuf, count)   # rebind-aware snapshot
        scratch = ()
        if alloc:
            shape = tuple(getattr(sendbuf, "shape", ()))
            reshape = int(np.prod(shape, dtype=np.int64)) == count
            wrap = isinstance(sendbuf, DeviceBuffer)

            def copyout(res):
                val = res if (not reshape or res.shape == shape) \
                    else res.reshape(shape)
                return DeviceBuffer(val) if wrap else val
        else:
            tgt = sendbuf if inplace else recvbuf
            if not isinstance(tgt, DeviceBuffer):
                return None         # jax.Array recv is immutable: legacy
            def copyout(res):
                v = tgt.value
                if is_jax_array(res) and res.size == v.size \
                        and res.dtype == v.dtype:
                    moved = tgt.setflat(res if res.shape == v.shape
                                        else res.reshape(v.shape))
                else:
                    moved = tgt.setflat(res, count)
                if moved:           # the result came from another chip
                    sc = _pv.scope()
                    if sc is not None:
                        _pv.note_moved(sc, False, res.nbytes)
                return tgt
    else:
        return None

    shm_release = None
    shm_bind = getattr(channel, "shm_bind", None)
    if shm_bind is not None:
        nbytes = int(count) * int(getattr(payload.dtype, "itemsize", 0) or 0)
        shm_release = shm_bind(nbytes)

    cid = comm.cid

    def nb_probe() -> int:
        st = _nb_state(ctx, cid, world_rank, create=False)
        return 0 if st is None else st.outstanding

    opname, hint, sig = cplan.opname, cplan.hint, cplan.sig
    runkw = {"unlocked_fold": True} if thread_tier else {}
    pv_nbytes = _pv.payload_nbytes(payload)

    pv_meta = ("allreduce", sig.get("algo"), sig.get("dtype"), pv_nbytes)

    def run_round():
        # the fast-armed Wait: one rendezvous round trip on THIS thread.
        # _ordered_run is unnecessary by construction — arming required an
        # idle nonblocking worker, and any later submission on this comm
        # demotes the armed round before it gets here.
        # Under ``Allreduce`` the op scope is the caller's (open since ITS
        # entry: the front door), which closes it; else it is ours
        own = _pv.op_begin() if _pv.enabled() else None
        sc = own or _pv.scope()
        if sc is not None:
            sc.lane, sc.meta = "armed", pv_meta
        try:
            res = channel.run(rank, contrib(), combine, opname,
                              plan=hint, **runkw)
            if sc is None:
                return copyout(res)
            t0 = _pv.monotonic()
            val = copyout(res)
            sc.spans.append(("copy", t0, _pv.monotonic()))
            _watch_copyout(sc, t0, val, across)
            return val
        finally:
            if own is not None:
                _pv.op_end(own, comm)

    # batched-submission hook (ISSUE 11): the pieces Waitall needs to
    # deposit K armed rounds through ONE rendezvous wakeup on the thread
    # tier (CollectiveChannel.run_batch). Proc-tier batching happens a
    # layer down (framed "batchv" coalescing in ProcChannel), so only the
    # thread tier publishes the parts.
    round_parts = None
    if thread_tier:
        round_parts = {
            "channel": channel, "rank": rank, "contrib": contrib,
            "combine": combine, "opname": opname, "hint": hint,
            "runkw": runkw, "copyout": copyout, "comm": comm,
            "sig": sig, "pv_nbytes": pv_nbytes,
        }

    return _registry.add(PlanRegistration(
        cid, config.GENERATION, run_round, scratch=scratch, wire=sendview,
        shm_release=shm_release, knob_on=True, nb_probe=nb_probe,
        inplace_optin=bool(inplace or (alloc and donate)),
        round_parts=round_parts))

def _persistent_round(req: PersistentCollRequest, fn):
    """Run one legacy-lane persistent round on the worker thread, tagging
    the collective event it records with the owning handle + round so
    ``analyze.explore`` models the round's timing from the Start/Wait pair
    instead of double-counting the inner event."""
    from .analyze import events as _ev
    if not _ev.enabled():
        return fn()
    with _ev.persistent_scope(id(req), req._round - 1):
        return fn()


def Allreduce_init(*args) -> PersistentCollRequest:
    """Persistent Allreduce (same flavors as :func:`Allreduce`). Arm with
    ``Start``/``Startall``; complete with the Wait/Test family; reuse. The
    allocating variant's value lands in ``req.result`` each round."""
    comm = _comm_of(args)
    req = PersistentCollRequest(
        lambda: _nb_submit(comm, lambda: _persistent_round(
            req, lambda: Allreduce(*args))),
        "pallreduce", args[0] if args else None, comm=comm)
    return req.bind_registration(lambda: _register_allreduce(comm, args))


def Bcast_init(buf: Any, root: int, comm: Comm) -> PersistentCollRequest:
    """Persistent Bcast of ``buf`` from ``root``; mutates buf every round."""
    req = PersistentCollRequest(
        lambda: _nb_submit(comm, lambda: _persistent_round(
            req, lambda: Bcast(buf, root, comm))),
        "pbcast", buf, comm=comm)
    return req


def Barrier_init(comm: Comm) -> PersistentCollRequest:
    """Persistent barrier."""
    req = PersistentCollRequest(
        lambda: _nb_submit(comm, lambda: _persistent_round(
            req, lambda: Barrier(comm))),
        "pbarrier", None, comm=comm)
    return req


