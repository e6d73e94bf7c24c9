"""Backend/platform introspection.

Reference: /root/reference/src/implementations.jl — queries
MPI_Get_library_version (:15-27), regex-parses vendor+version into an MPIImpl
enum (:57-66,80-132), and exposes MPI_VERSION (:154-170). The TPU analog
(SURVEY.md §2.1): identify the accelerator platform (TPU generation / CPU sim),
the runtime library (jax/jaxlib/libtpu versions), and the interconnect
topology, so programs can adapt like MPI programs adapt to MPICH vs OpenMPI.
"""

from __future__ import annotations

import enum
import functools
import re
from typing import Optional


class Backend(enum.Enum):
    """The transport 'implementation' (analog of MPIImpl, implementations.jl:57-66)."""
    UNKNOWN = 0
    CPU_SIM = 1        # fake XLA CPU devices (test substrate, SURVEY.md §3.5)
    TPU = 2            # real TPU chips over ICI
    GPU = 3            # jax on GPU (works, but not the design target)


# Pattern table: device-kind string -> TPU generation (the analog of the
# vendor version-string regexes in implementations.jl:80-132).
_TPU_KINDS = [
    (re.compile(r"v6|trillium", re.I), "v6"),
    (re.compile(r"v5p", re.I), "v5p"),
    (re.compile(r"v5e|v5 ?lite", re.I), "v5e"),
    (re.compile(r"v4", re.I), "v4"),
    (re.compile(r"v3", re.I), "v3"),
    (re.compile(r"v2", re.I), "v2"),
]


@functools.lru_cache(maxsize=1)
def _devices():
    import jax
    return jax.devices()


def get_backend() -> Backend:
    """Which transport backs the job (implementations.jl MPI_LIBRARY analog)."""
    try:
        platform = _devices()[0].platform
    except Exception:
        return Backend.UNKNOWN
    if platform == "tpu":
        return Backend.TPU
    if platform == "cpu":
        return Backend.CPU_SIM
    if platform in ("gpu", "cuda", "rocm"):
        return Backend.GPU
    return Backend.UNKNOWN


def tpu_generation() -> Optional[str]:
    """'v5e' / 'v5p' / … or None off-TPU (the per-generation capability key
    SURVEY.md §2.4 asks for)."""
    if get_backend() is not Backend.TPU:
        return None
    kind = _devices()[0].device_kind
    for pat, gen in _TPU_KINDS:
        if pat.search(kind):
            return gen
    return None


def Get_library_version() -> str:
    """Version string of the runtime stack (implementations.jl:15-27)."""
    import jax
    import jaxlib
    parts = [f"jax {jax.__version__}", f"jaxlib {jaxlib.__version__}"]
    try:
        d = _devices()[0]
        parts.append(f"platform {d.platform} ({d.device_kind})")
    except Exception:
        pass
    return ", ".join(parts)


def Get_version() -> tuple[int, int]:
    """API version of this framework (implementations.jl:154-170 reports the
    MPI standard version; we report the capability surface we mirror)."""
    return (3, 1)


def device_count() -> int:
    return len(_devices())


def ici_topology() -> Optional[tuple[int, ...]]:
    """Physical torus coordinates bounds of the local slice, when the runtime
    exposes them (None on CPU sim). Used for torus-aware Dims_create."""
    try:
        devs = _devices()
        coords = [getattr(d, "coords", None) for d in devs]
        if any(c is None for c in coords):
            return None
        dims = tuple(max(c[i] for c in coords) + 1 for i in range(len(coords[0])))
        return dims
    except Exception:
        return None


# ---------------------------------------------------------------------------
# Per-generation capability tables — the analog of the pre-baked ABI constant
# tables deps/consts_mpich.jl / consts_openmpi.jl / consts_microsoftmpi.jl
# (SURVEY.md §2.4): public chip-level numbers programs and benchmarks consult
# to contextualize measurements (aggregate one-way ICI GB/s per chip, HBM
# GB/s and capacity per chip, TensorCores per chip, peak bf16 TFLOP/s).
# ---------------------------------------------------------------------------

CAPABILITIES: dict[str, dict[str, float]] = {
    "v2":  {"ici_gbps": 62.5,  "hbm_gbps": 300.0,  "hbm_gib": 16.0,
            "cores": 2, "bf16_tflops": 46.0},
    "v3":  {"ici_gbps": 112.5, "hbm_gbps": 450.0,  "hbm_gib": 32.0,
            "cores": 2, "bf16_tflops": 123.0},
    "v4":  {"ici_gbps": 270.0, "hbm_gbps": 1228.0, "hbm_gib": 32.0,
            "cores": 2, "bf16_tflops": 275.0},
    "v5e": {"ici_gbps": 180.0, "hbm_gbps": 819.0,  "hbm_gib": 16.0,
            "cores": 1, "bf16_tflops": 197.0},
    "v5p": {"ici_gbps": 540.0, "hbm_gbps": 2765.0, "hbm_gib": 95.0,
            "cores": 2, "bf16_tflops": 459.0},
    "v6":  {"ici_gbps": 448.0, "hbm_gbps": 1638.0, "hbm_gib": 32.0,
            "cores": 1, "bf16_tflops": 918.0},
}


def platform_probe() -> dict:
    """One-shot platform report — the runtime analog of the reference's
    build-time ``gen_consts`` probe (/root/reference/deps/gen_consts.jl:
    compiled and executed under mpiexec to discover the ABI's constants).
    Here the 'ABI' is the accelerator platform: backend, TPU generation,
    device inventory with physical coords, torus bounds, process metadata,
    and the generation's capability constants. ``tpurun --probe`` prints it
    as JSON."""
    report: dict = {
        "backend": get_backend().name,
        "library_version": Get_library_version(),
        "api_version": list(Get_version()),
        "generation": tpu_generation(),
        "device_count": device_count(),
        "ici_topology": (list(ici_topology()) if ici_topology() else None),
        "capabilities": (capabilities() if tpu_generation() else None),
    }
    try:
        import jax
        report["devices"] = [{
            "id": d.id,
            "kind": getattr(d, "device_kind", "?"),
            "process": getattr(d, "process_index", 0),
            "coords": (list(d.coords)
                       if getattr(d, "coords", None) is not None else None),
            "core_on_chip": getattr(d, "core_on_chip", None),
        } for d in _devices()]
        report["process_count"] = jax.process_count()
        report["process_index"] = jax.process_index()
    except Exception:
        pass
    return report


def capabilities(generation: Optional[str] = None) -> dict[str, float]:
    """Capability row for a generation (default: the local chip). A device
    that is not in the table is an error, not a default: a ratio against
    another chip's peak is not a measurement."""
    gen = generation or tpu_generation()
    if gen not in CAPABILITIES:
        raise KeyError(f"no capability row for TPU generation {gen!r}; "
                       f"known: {', '.join(sorted(CAPABILITIES))}")
    return dict(CAPABILITIES[gen])


MPI_LIBRARY = "tpu_mpi"
