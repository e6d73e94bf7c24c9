"""Buffers: the array-type registry and send/recv operand normalization.

Reference: /root/reference/src/buffers.jl — MPIBuffertype union (:9), MPIPtr
conversion (:13-23), @assert_minlength bounds guard (:25-31), the
Buffer(data,count,datatype) triple (:78-91) with constructors for arrays, Refs
and three SubArray flavors that auto-derive vector/subarray datatypes
(:101-117), Buffer_send for isbits scalars (:125), and the CUDA extension
(src/cuda.jl:6-28) that plugs device arrays into the same conversion.

TPU mapping (SURVEY.md §2.2/§2.3): a buffer is either a host numpy array
(mutable, views welcome — numpy's strided views subsume the reference's
auto-derived SubArray datatypes) or a device-resident jax.Array. jax.Arrays are
immutable, so the mutating API accepts :class:`DeviceBuffer`, a thin rebinding
cell whose ``__setitem__`` lowers to functional ``.at[].set`` updates — the
pluggable array-registry pattern BASELINE.json asks for, with numpy and jax
registered by default.
"""

from __future__ import annotations

import weakref
from typing import Any, Optional

import numpy as np

from .datatypes import Datatype, to_datatype
from . import error as _ec
from .error import MPIError

# Host arrays created by to_wire as private snapshots — explicitly marked so
# in-place consumers (the multi-process ring allreduce) key their
# no-second-copy fast path on provenance, not on inferred numpy flags that a
# future caller's owning-but-shared array could also satisfy (ADVICE r2).
# Keyed by id with weakly-referenced values (ndarrays are weakref-able but
# not hashable): an entry dies with its array, so marking never extends a
# snapshot's lifetime and a recycled id can never alias a live entry.
_wire_snapshots: "weakref.WeakValueDictionary[int, np.ndarray]" = \
    weakref.WeakValueDictionary()


def _mark_wire_snapshot(arr: np.ndarray) -> np.ndarray:
    _wire_snapshots[id(arr)] = arr
    return arr


def is_wire_snapshot(arr: Any) -> bool:
    """True iff ``arr`` is a private host copy minted by :func:`to_wire`
    (safe to mutate in place: no user alias can exist)."""
    return _wire_snapshots.get(id(arr)) is arr


class _InPlace:
    """Sentinel for in-place collectives (src/collective.jl:1 IN_PLACE)."""

    def __repr__(self) -> str:
        return "IN_PLACE"


IN_PLACE = _InPlace()
BUFFER_NULL = None


def is_jax_array(x: Any) -> bool:
    return type(x).__module__.startswith("jax") and hasattr(x, "dtype")


def _device_dtype(dtype: Any, device: Any = None):
    """The dtype a device array of declared ``dtype`` gets, or a typed error
    when the device cannot hold it faithfully. With ``jax_enable_x64`` off
    (the default outside the test suite) jax narrows 64-bit input to 32
    bits; the byte-level paths (file I/O, RMA windows, datatype extents)
    size everything from the declared dtype, so a narrowed operand would
    corrupt them silently. With it on, a TPU still has no 64-bit floating
    type: float64 comes back changed (measured on the v5e, PR 21: neither a
    round trip nor x + x is bit-exact) and complex128 aborts the compiler;
    int64/uint64 are exact."""
    import jax
    canon = jax.dtypes.canonicalize_dtype(dtype)
    if canon.itemsize != np.dtype(dtype).itemsize:
        raise MPIError(
            f"device buffer of {np.dtype(dtype)} would be narrowed to "
            f"{canon}: enable 64-bit device arrays "
            f"(jax.config.update('jax_enable_x64', True) or JAX_ENABLE_X64=1) "
            f"or convert the operand to a 32-bit dtype first",
            code=_ec.ERR_TYPE)
    if canon.kind in "fc" and canon.itemsize >= 8 and (
            getattr(device, "platform", None)
            or jax.default_backend()) == "tpu":
        raise MPIError(
            f"a TPU cannot hold {canon} exactly (it has no 64-bit floating "
            f"type): keep the operand on the host as a numpy array, or "
            f"convert it to float32/complex64 first", code=_ec.ERR_TYPE)
    return canon


def on_sharding(x: Any, sharding: Any):
    """``x`` (a jax.Array, or host data) as a jax.Array on ``sharding`` —
    a device-to-device copy when a collective's operand or result sits on
    another rank's chip, an upload for host data, and nothing at all when it
    already is there (the single-chip and CPU-sim default-placement case)."""
    if is_jax_array(x) and x.sharding == sharding:
        return x
    import jax
    return jax.device_put(x, sharding)


def _onto(src: Any, like: Any):
    """Any array-like ``src`` as a jax.Array of ``like``'s dtype on
    ``like``'s device(s); host data goes straight there."""
    if not is_jax_array(src):
        src = np.asarray(src, dtype=like.dtype)
    elif src.dtype != like.dtype:
        src = src.astype(like.dtype)
    return on_sharding(src, like.sharding)


class DeviceBuffer:
    """A mutable cell holding a device-resident jax.Array.

    The analog of passing a CuArray to MPI.jl (src/cuda.jl:26-28): device data
    is a first-class communication operand. Mutation rebinds via functional
    updates, so the mutating API (Recv!, Allreduce! with a recv buffer, …)
    works identically for host and device arrays. The cell stays on the
    device it was created on (``device=comm.device`` binds it to the calling
    rank's chip): whatever is written into it is moved there.
    """

    def __init__(self, value: Any, dtype: Any = None, device: Any = None):
        import jax.numpy as jnp
        # Python scalars and lists declare no dtype and take jax's default
        want = dtype if dtype is not None else getattr(value, "dtype", None)
        if want is not None:
            dtype = _device_dtype(want, device)
        arr = jnp.asarray(value, dtype=dtype)
        if device is not None:
            import jax
            arr = jax.device_put(arr, device)
        self.value = arr

    # -- constructors mirroring ArrayType{T}(undef, dims) test usage ---------
    @classmethod
    def empty(cls, shape: Any, dtype: Any = np.float64,
              device: Any = None) -> "DeviceBuffer":
        import jax.numpy as jnp
        return cls(jnp.zeros(shape, dtype=_device_dtype(dtype, device)),
                   device=device)

    @property
    def shape(self):
        return self.value.shape

    @property
    def dtype(self):
        return self.value.dtype

    @property
    def size(self) -> int:
        return int(self.value.size)

    def __len__(self) -> int:
        return int(self.value.shape[0]) if self.value.ndim else 0

    def __array__(self, dtype=None):
        out = np.asarray(self.value)
        return out.astype(dtype) if dtype is not None else out

    def __getitem__(self, idx):
        return self.value[idx]

    def __setitem__(self, idx, val):
        if is_jax_array(val):
            val = on_sharding(val, self.value.sharding)
        self.value = self.value.at[idx].set(val)

    def setflat(self, src: Any, count: Optional[int] = None) -> bool:
        """Assign the first ``count`` flat elements from src. Returns True
        where that enqueued a copy of a whole jax array from another
        device (the collectives count those; the compare is made once)."""
        v = self.value
        # Fast path: full replacement by an identically-shaped jax array is a
        # pure rebind — no device dispatch at all. This is the hot lane of the
        # host-path collectives (the combined result is handed straight back).
        if (is_jax_array(src) and src.dtype == v.dtype and src.shape == v.shape
                and (count is None or count == v.size)):
            if src.sharding == v.sharding:
                self.value = src
                return False
            import jax
            self.value = jax.device_put(src, v.sharding)
            return True
        import jax.numpy as jnp
        n = (count if count is not None
             else int(np.prod(np.shape(src), dtype=np.int64)))
        if n == v.size and v.shape == tuple(np.shape(src)):
            self.value = _onto(src, v)
        else:
            flat = jnp.ravel(_onto(src, v))
            out = jnp.ravel(v).at[:n].set(flat[:n])
            self.value = out.reshape(v.shape)
        return False

    def copy(self) -> "DeviceBuffer":
        return DeviceBuffer(self.value)

    def fill(self, v: Any) -> None:
        import jax.numpy as jnp
        self.value = jnp.full_like(self.value, v)

    def __repr__(self) -> str:
        return f"DeviceBuffer({self.value!r})"


class Buffer:
    """(data, count, datatype) communication operand (src/buffers.jl:78-91)."""

    def __init__(self, data: Any, count: Optional[int] = None,
                 datatype: Optional[Datatype] = None):
        self.data = data
        arr = extract_array(data)
        if arr is None:
            raise MPIError(f"not a communication buffer: {type(data).__name__}",
                           code=_ec.ERR_BUFFER)
        self.count = count if count is not None else int(arr.size)
        self.datatype = datatype if datatype is not None else to_datatype(arr.dtype)

    @property
    def array(self):
        return extract_array(self.data)


def Buffer_send(x: Any) -> Buffer:
    """Normalize any send operand, incl. scalars (src/buffers.jl:125)."""
    if isinstance(x, Buffer):
        return x
    if np.isscalar(x) or isinstance(x, (int, float, complex, bool, np.generic)):
        return Buffer(np.asarray(x))
    return Buffer(x)


def extract_array(x: Any):
    """The underlying numpy/jax array of an operand, or None.

    The array-type registry: numpy arrays (incl. non-contiguous views — strided
    views play the role of the reference's auto-derived SubArray datatypes,
    src/buffers.jl:101-117), jax.Arrays, DeviceBuffer cells, scalars, and
    nested sequences.
    """
    if isinstance(x, DeviceBuffer):
        return x.value
    if isinstance(x, np.ndarray) or is_jax_array(x):
        return x
    if isinstance(x, (np.generic, int, float, complex, bool)):
        return np.asarray(x)
    if isinstance(x, (list, tuple)) and x and not isinstance(x[0], (list, tuple)):
        return None  # plain sequences must be wrapped explicitly to avoid surprises
    return None


def element_count(x: Any) -> int:
    arr = extract_array(x)
    if arr is None:
        raise MPIError(f"not a communication buffer: {type(x).__name__}",
                       code=_ec.ERR_BUFFER)
    return int(arr.size)


def assert_minlength(buf: Any, count: int) -> None:
    """Bounds guard; raises AssertionError like the reference's
    @assert_minlength (src/buffers.jl:25-31)."""
    n = element_count(buf)
    assert n >= count, f"buffer has {n} elements, needs at least {count}"


def is_writable(x: Any) -> bool:
    if isinstance(x, DeviceBuffer):
        return True
    if isinstance(x, np.ndarray):
        return x.flags.writeable
    return False


def write_flat(dest: Any, src: Any, count: Optional[int] = None) -> Any:
    """Write the first ``count`` flat elements of src into dest.

    dest: numpy array (strided views fine) or DeviceBuffer. Returns dest.
    """
    if isinstance(dest, DeviceBuffer):
        dest.setflat(src, count)    # (who counts copies calls it directly)
        return dest
    if isinstance(dest, np.ndarray):
        srcarr = np.asarray(src)
        n = srcarr.size if count is None else count
        if n == dest.size and srcarr.size == dest.size:
            # strided-safe elementwise assignment
            dest[...] = srcarr.reshape(dest.shape).astype(dest.dtype, copy=False) \
                if srcarr.shape != dest.shape else srcarr.astype(dest.dtype, copy=False)
        elif dest.flags.c_contiguous:
            # contiguous: reshape(-1) is a VIEW, and direct slice assignment
            # is a memcpy — ndarray.flat's iterator assignment is ~8x slower
            # at MiB sizes, which dominates the RMA bulk path
            dest.reshape(-1)[:n] = srcarr.reshape(-1)[:n]
        else:
            # ndarray.flat is a logical C-order view regardless of the
            # underlying strides, so partial writes land at the right logical
            # positions even for reversed/transposed/F-ordered views.
            dest.flat[:n] = srcarr.reshape(-1)[:n]
        return dest
    if is_jax_array(dest):
        raise MPIError("jax.Array is immutable; wrap it in DeviceBuffer for "
                       "the mutating API, or use the allocating variant",
                       code=_ec.ERR_BUFFER)
    raise MPIError(f"cannot write into {type(dest).__name__}", code=_ec.ERR_BUFFER)


def write_range(buf: Any, off: int, new: np.ndarray) -> None:
    """Write 1-d ``new`` into the flat element range [off, off+len(new)) of a
    window-exposable buffer (the RMA write primitive: onesided.Put /
    Accumulate and the multi-process owner apply path share it). DeviceBuffer
    targets rebind the whole array; host arrays write in place."""
    n = int(np.asarray(new).size)
    if isinstance(buf, DeviceBuffer):
        flat = buf.value.reshape(-1).at[off:off + n].set(
            np.asarray(new, dtype=buf.value.dtype))
        buf.value = flat.reshape(buf.value.shape)
    else:
        arr = extract_array(buf)
        if arr is None:
            raise MPIError(f"cannot write into {type(buf).__name__}")
        tgt = np.asarray(arr)
        if tgt.flags.c_contiguous:
            # contiguous: reshape(-1) is a VIEW and slice assignment is a
            # memcpy; .flat's iterator assignment is ~8x slower at MiB sizes
            tgt.reshape(-1)[off:off + n] = new
        else:
            # .flat is a logical C-order view regardless of strides —
            # reshape(-1) on a non-contiguous view would copy and silently
            # drop the write
            tgt.flat[off:off + n] = new


def resolve_attached(attached, addr: int, who: str):
    """Resolve a dynamic-window byte address against an attach list of
    (base_addr, nbytes, buf) entries → (buf, array, element offset). Shared
    by the in-process and multi-process dynamic-window paths
    (src/onesided.jl:109-121 addressing contract)."""
    addr = int(addr)
    for (base_addr, nbytes, buf) in attached:
        if base_addr <= addr < base_addr + nbytes:
            arr = extract_array(buf)
            off = (addr - base_addr) // arr.dtype.itemsize
            return buf, arr, int(off)
    raise MPIError(f"address {addr:#x} not attached on rank {who}")


def clone_like(x: Any, value: Any) -> Any:
    """An operand of the same registry kind as x holding ``value``."""
    if isinstance(x, DeviceBuffer):
        out = DeviceBuffer(value)
        out.value = on_sharding(out.value, x.value.sharding)
        return out
    if is_jax_array(x):
        import jax.numpy as jnp
        return on_sharding(jnp.asarray(value), x.sharding)
    return np.array(value, copy=True)


def to_wire(x: Any, count: Optional[int] = None) -> Any:
    """A contiguous, immutable-by-convention snapshot of a send operand.

    Host arrays are copied (the sender may mutate after a buffered Isend);
    device arrays are immutable so the reference is the snapshot — the zero-copy
    win of device-native buffers (SURVEY.md L5).

    With ``count``, host snapshots come back FLAT and OWNING (base None,
    owndata) in a single copy — downstream in-place consumers (the
    multi-process ring allreduce) key their no-second-copy fast path on
    those flags, and a flat view of a private copy would defeat it.
    """
    if isinstance(x, DeviceBuffer):
        arr = x.value
    elif is_jax_array(x):
        arr = x
    else:
        src = np.asarray(x)
        if count is None:
            arr = np.ascontiguousarray(src)
            return _mark_wire_snapshot(arr.copy() if arr is src else arr)
        out = np.ravel(src)           # view (contiguous) or owning copy
        if out.size != count:
            out = out[:count]
        if out.base is not None or out is src:
            out = out.copy()          # the single snapshot copy
        return _mark_wire_snapshot(out)
    if count is not None:
        shape = arr.shape
        if len(shape) == 1 and shape[0] == count:
            return arr
        flat = arr.reshape(-1)
        return flat if flat.size == count else flat[:count]
    return arr


def wire_view(x: Any, count: Optional[int] = None) -> Any:
    """A contiguous flat VIEW of a send operand — the zero-copy sibling of
    :func:`to_wire` for contributions whose rendezvous output is always a
    FRESH array (the reduce-family fold): every rank stays blocked in the
    rendezvous until the fold has run, so the live buffer cannot change
    under the combiner, and nothing downstream retains the view after the
    pick. Deliberately NOT marked as a wire snapshot — in-place consumers
    (the multi-process ring allreduce) must still copy before mutating.
    Falls back to :func:`to_wire` when a flat view can't be taken without a
    copy (non-contiguous host views), so callers always get wire shape."""
    if isinstance(x, DeviceBuffer) or is_jax_array(x):
        return to_wire(x, count)      # device refs are already zero-copy
    src = np.asarray(x)
    if not src.flags.c_contiguous:
        return to_wire(x, count)
    flat = src.reshape(-1)
    if count is not None and flat.size != count:
        flat = flat[:count]
    return flat


# Registered (pinned) host scratch arrays, minted by register_scratch() for
# the persistent-collective fast path (docs/performance.md "Registered
# buffers"): private to the runtime, never aliased by user data, so folds
# may mutate them in place round after round with zero steady-state
# allocation. Same id-keyed weak marking scheme as _wire_snapshots.
_registered: "weakref.WeakValueDictionary[int, np.ndarray]" = \
    weakref.WeakValueDictionary()


def register_scratch(count: int, dtype: Any) -> np.ndarray:
    """A pinned, runtime-private flat host array for a plan-bound fold
    accumulator. Registered buffers are allocated once at plan creation
    (``Allreduce_init``) and reused by every round — the zero-alloc
    contract the registered fast path is built on."""
    arr = np.empty(int(count), dtype=np.dtype(dtype))
    _registered[id(arr)] = arr
    return arr


def is_registered(arr: Any) -> bool:
    """True iff ``arr`` is a runtime-private registered scratch buffer
    (safe to fold into in place; no user alias can exist)."""
    return _registered.get(id(arr)) is arr


def pinned_wire_view(x: Any, count: int) -> Optional[np.ndarray]:
    """A STABLE flat view of a host send operand, bindable once at plan
    creation: later rounds reuse the view with no per-call normalization.
    Returns None when the operand cannot be pre-bound — non-ndarray kinds
    (DeviceBuffer rebinds its array every round; jax arrays are replaced,
    not mutated), non-contiguous views (wire_view would copy), or object
    dtype. The caller falls back to per-call :func:`wire_view`."""
    if not isinstance(x, np.ndarray) or x.dtype == object:
        return None
    if not x.flags.c_contiguous:
        return None
    flat = x.reshape(-1)
    return flat if flat.size == count else flat[:count]


_POISON_BYTE = 0xA5


def poison_fill(buf: Any, count: Optional[int] = None) -> None:
    """Fill the first ``count`` flat elements of an origin buffer with a loud
    sentinel (strict mode, docs/performance.md "Batched read epochs"): floats
    and complexes become NaN, ints the repeated-0xA5 bit pattern — so a
    caller consuming a deferred Get/Fetch_and_op origin before the closing
    synchronization sees obviously-poisoned values (NaN propagates;
    0xA5A5… is unmistakable) instead of plausible stale data. Object-dtype
    and other unpoisonable operands are left untouched."""
    arr = extract_array(buf)
    if arr is None:
        return
    n = int(arr.size if count is None else min(int(count), arr.size))
    if n <= 0:
        return
    dt = np.dtype(arr.dtype)
    if dt.kind == "f":
        val = dt.type(np.nan)
    elif dt.kind == "c":
        val = dt.type(complex(np.nan, np.nan))
    elif dt.kind in "iub":
        val = np.frombuffer(bytes([_POISON_BYTE]) * dt.itemsize, dtype=dt)[0]
    else:
        return
    if isinstance(buf, DeviceBuffer):
        write_range(buf, 0, np.full(n, val, dtype=dt))
    elif isinstance(buf, np.ndarray):
        if buf.flags.c_contiguous:
            buf.reshape(-1)[:n] = val
        else:
            buf.flat[:n] = val


# The reference's dispatch unions (src/buffers.jl:1-11) as isinstance()
# tuples. Deliberate divergences from the Julia unions: native Python
# scalars (int/float/complex/bool) are included — the typed send path
# accepts them — and numpy bools are in MPIDatatype (BOOL is a predefined
# datatype here) while Julia's Char has no scalar Python analog (1-char
# strings travel on the object path instead). Python-ism to know: bool
# subclasses int, so isinstance(True, MPIInteger) is True (Julia's Bool
# is not in its MPIInteger) — dispatch that must distinguish bools checks
# them BEFORE the integer union.
MPIInteger = (int, np.int8, np.uint8, np.int16, np.uint16,
              np.int32, np.uint32, np.int64, np.uint64)
MPIFloatingPoint = (float, np.float32, np.float64, np.float16)
MPIComplex = (complex, np.complex64, np.complex128)
MPIDatatype = (bool, np.bool_) + MPIInteger + MPIFloatingPoint + MPIComplex
