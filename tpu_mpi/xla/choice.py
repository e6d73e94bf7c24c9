"""Which of a Pallas kernel and its plain path a traced program gets.

Every mechanism of the train step that has a kernel has a plain path beside
it: the only one that runs off a TPU (the tests, the CPU rehearsals) and
outside the kernel's contract, and what the tests hold the kernel against.
The choice between the two is made here, by one rule:

    a kernel runs where a backend is there (:func:`backend`) and the
    kernel's contract takes the operands; it is decided before the call,
    from the backend and the shapes and types alone, and never by trying
    the kernel and catching the failure: once selected, a kernel that does
    not lower is an error. What was decided is counted in `perfvars` under
    the choice's family, and the caller is handed the ``interpret`` flag
    to pass on to the kernel.

A contract lives in its kernel's file (``*_blocks``, ``*_selected``: it
holds the kernel to its own operand types) and says None or False where the
kernel does not take the operands, else something true that the caller may
need (a kernel's tiles). :func:`fit` is the rule as a question,
:func:`decide` the rule where the call is made; how a new kernel joins is
in docs/observability.md, beside the table of the families.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, NamedTuple, Optional

import jax

from .. import perfvars
from . import pallas_kernels as pk
from . import (conv_kernels, delta_kernels, head_norm_kernels,
               sel_scan_kernels, ssm_kernels)


def backend() -> Optional[str]:
    """How a selected kernel would run here: "mosaic" on a TPU, None
    elsewhere (no kernel is selected). The tests set the word for the time
    of a trace (`tests/conftest.py`, ``kernel_backend``), "interpret" among
    them: the Pallas interpret machine, which is far too slow to be chosen."""
    return "mosaic" if jax.default_backend() == "tpu" else None


def interpret() -> bool:
    """The flag a selected kernel is called with. (Asked by itself where a
    choice is acted on later in its trace: `parallel.ep._summed`.)"""
    return backend() == "interpret"


def trace_key() -> tuple:
    """Everything a choice reads while a program is traced, beside its
    operands: what a cache of traced functions that hold a choice is keyed
    on (`models.transformer._block_traced_once`, `parallel.ep._summed`), so
    that a trace made under one answer is not found under another. A rule
    that comes to read something else adds it here. (A builder under `xla/`
    reads nothing: it is cached on the ``interpret`` it is handed.)"""
    return (backend(),)


class Choice(NamedTuple):
    """One choice between a kernel and its plain path: the kernel's
    ``contract``, the ``family`` of `perfvars.FAMILIES` it is counted in and
    the kinds it counts as where the ``kernel`` runs and where the ``plain``
    path does (None: the plain path counts its own form); ``by``, where
    there is one, the family that counts the same again by what the call
    was ``of``."""
    contract: Callable
    family: str
    kernel: str
    plain: Optional[str]
    by: Optional[str] = None


# `parallel.ring.local_attention`
ATTENTION = Choice(pk.causal_attention_blocks, "attn_lowerings", "fused",
                   "plain", by="attn_kinds")
# `parallel.ep.grouped_products`
GROUPED = Choice(pk.grouped_matmul_blocks, "gmm_lowerings", "kernel",
                 "ragged_dot")
# `parallel.ep.sum_rows` / `rows_at`
ROW_SUM = Choice(pk.grouped_row_sums_blocks, "row_sum_lowerings", "product",
                 "scatter")
# `models.transformer._rope_heads` and `_norm_and_rope`: the plain path is
# `_rope` on each rotated part, which counts the form it takes
ROPE_HEADS = Choice(pk.rope_heads_blocks, "rope_forms", "dense", None)
NORM_ROPE = Choice(pk.norm_rope_blocks, "rope_forms", "dense", None)
# `parallel.ssm.scan` and `selective_scan`
SCAN = Choice(ssm_kernels.ssm_scan_selected, "scan_kernel_lowerings",
              "kernel", "plain")
SEL_SCAN = Choice(sel_scan_kernels.sel_scan_selected,
                  "sel_scan_kernel_lowerings", "kernel", "plain")
# `parallel.delta.delta_scan`
DELTA_SCAN = Choice(delta_kernels.delta_scan_selected,
                    "delta_kernel_lowerings", "kernel", "plain")
# `parallel.ssm.conv_silu`
CONV = Choice(conv_kernels.conv_silu_blocks, "conv_kernel_lowerings",
              "kernel", "plain")
# `models.transformer._l2_normed` and `_head_norm_gated`
HEAD_NORM = Choice(head_norm_kernels.head_norm_blocks, "head_norm_lowerings",
                   "kernel", "plain")


class Run(NamedTuple):
    """A kernel was selected: what its contract said of the operands (its
    tiles, or True), and the flag it is to be called with."""
    fit: Any
    interpret: bool


def fit(choice: Choice, *operands, also: bool = True):
    """What ``choice``'s contract says of ``operands`` where its kernel is
    selected, else None: the rule above as a question, nothing counted.
    ``also``: a condition of the caller's own that must hold as well (its
    places in whole blocks, a part that is rotated)."""
    if not also or backend() is None:
        return None
    return choice.contract(*operands) or None


def decide(choice: Choice, *operands, also: bool = True, count: int = 1,
           of: Optional[str] = None) -> Optional[Run]:
    """The rule where the call is made: a :class:`Run` where the kernel is
    selected for ``operands`` (:func:`fit`), None where the plain path runs,
    counted ``count`` times under the choice's family either way, and where
    the call is ``of`` a kind again under that."""
    got = fit(choice, *operands, also=also)
    kind = choice.plain if got is None else choice.kernel
    if kind is not None:
        perfvars.note(choice.family, kind, count)
        if of is not None:
            perfvars.note(choice.by, (of, kind), count)
    return None if got is None else Run(got, interpret())


def warm_kernel_imports() -> None:
    """Where a kernel can be selected, start importing Pallas on a thread:
    the import costs 0.8 s (it pulls in the GPU and Mosaic dialects) and
    would otherwise be paid inside the first trace of a step. A builder of
    a step calls this; the trace then finds the modules there, or waits on
    the import lock for what is left."""
    if backend() is not None:
        def load():     # set-up, but no arming: a span alone, in no pvar
            t0 = perfvars.monotonic()
            pk.load()
            perfvars.publish_setup_span("kernels.import", t0,
                                        perfvars.monotonic())
        threading.Thread(target=load, name="tpu_mpi-pallas-import",
                         daemon=True).start()
