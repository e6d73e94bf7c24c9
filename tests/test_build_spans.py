"""Set-up's own spans and counters (PR 34, docs/observability.md "Set-up
spans"): the one `jax.monitoring` listener behind the pvar family ``build``
(every trace, lowering, backend compile and cache read of the process, by
function), the ``build.*`` spans it publishes under the open ``setup_span``
while span sampling is on, and the count of Pallas kernels built under a
trace. On the CPU backend; nothing here is a timing."""

import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import pytest
from jax import monitoring

from tpu_mpi import config, perfvars, tracectx, xla
from tpu_mpi.models.transformer import (TransformerConfig, transformer_init,
                                        transformer_train_step)
from tpu_mpi.xla import choice
from tpu_mpi.xla import pallas_kernels as pk

PHASES = ("trace", "lower", "compile")
COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE = "/jax/compilation_cache/"
ZERO = {"n": 0, "s": 0.0}


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.delenv("TPU_MPI_PVARS", raising=False)
    monkeypatch.delenv("TPU_MPI_TRACE_SAMPLE", raising=False)
    config.load(refresh=True)
    perfvars.pcontrol(1)
    assert perfvars.listen_builds()
    perfvars.reset()
    tracectx.reset()
    yield
    monkeypatch.delenv("TPU_MPI_TRACE_SAMPLE", raising=False)
    config.load(refresh=True)
    perfvars.pcontrol(1)
    perfvars.reset()
    tracectx.reset()


def _sample(monkeypatch, rate=1):
    monkeypatch.setenv("TPU_MPI_TRACE_SAMPLE", str(rate))
    config.load(refresh=True)


def _operand(n):
    """An array made before the counters are read: making it builds
    programs of its own."""
    x = jax.block_until_ready(jnp.arange(n, dtype=jnp.float32))
    perfvars.reset()
    return x


def _build():
    return perfvars.snapshot()["build"]


def _counts(name):
    row = _build()["by_fun"].get(name, {})
    return tuple(row.get(p, ZERO)["n"] for p in PHASES)


def _setup_spans():
    return [s for s in tracectx.drain() if s["trace"].startswith("setup:")]


def test_a_compile_counts_under_the_functions_name():
    def cubed_plus_one(x):
        return x * x * x + 1
    f = jax.jit(cubed_plus_one)
    x3, x5 = _operand(3), _operand(5)
    assert _build() == {}
    jax.block_until_ready(f(x3))
    assert _counts("cubed_plus_one") == (1, 1, 1)
    row = _build()["by_fun"]["cubed_plus_one"]
    assert all(row[p]["s"] > 0 for p in PHASES)
    # the lowered module and the compile are named `jit(f)`, the trace `f`
    assert not any(k.startswith("jit") for k in _build()["by_fun"])
    # the same shapes again build nothing; a new shape builds one of each
    before = _build()
    jax.block_until_ready(f(x3))
    assert _build() == before
    jax.block_until_ready(f(x5))
    assert _counts("cubed_plus_one") == (2, 2, 2)
    total = _build()
    assert all(total[p]["n"] == 2 for p in PHASES)
    assert total["step"] == [] and total["kernels"] == {}


def test_a_trace_inside_a_trace_is_in_its_callers_seconds_only():
    @jax.jit
    def inner_fn(x):
        return x + 2

    @jax.jit
    def outer_fn(x):
        return inner_fn(x) * 3
    x = _operand(4)
    jax.block_until_ready(outer_fn(x))
    assert _counts("outer_fn") == (1, 1, 1)
    assert _counts("inner_fn") == (1, 0, 0)     # a function of outer's module
    got = _build()
    assert got["trace"]["n"] == 1               # the outermost alone
    assert got["trace"]["s"] == got["by_fun"]["outer_fn"]["trace"]["s"]
    assert got["by_fun"]["inner_fn"]["trace"]["s"] <= got["trace"]["s"]


def test_cache_events_fill_the_cache_block():
    """The CPU backend has no persistent cache to hit: the listener is fed
    what JAX would send."""
    monitoring.record_event(CACHE + "compile_requests_use_cache")
    monitoring.record_event(CACHE + "cache_hits")
    monitoring.record_event_duration_secs(CACHE + "cache_retrieval_time_sec", 0.25)
    monitoring.record_event_duration_secs(CACHE + "compile_time_saved_sec", 4.0)
    monitoring.record_event(CACHE + "compile_requests_use_cache")
    monitoring.record_event(CACHE + "cache_misses")
    monitoring.record_event(CACHE + "cache_hits")
    monitoring.record_event_duration_secs(CACHE + "cache_retrieval_time_sec", 0.5)
    monitoring.record_event_duration_secs("/some/other/duration", 9.0)
    assert _build()["cache"] == {"hits": 2, "misses": 1, "load_s": 0.75,
                                 "saved_s": 4.0}
    assert _build()["by_fun"] == {}
    # (every answer of the cache is followed by its compile's end, which
    # takes it: JAX sends that one even where the compile raised)
    monitoring.record_event_time_span(COMPILE, 0.0, 1.0, fun_name="jit(f)")


@pytest.mark.parametrize("events, want", [
    ((), "off"),
    (("compile_requests_use_cache",), "miss"),
    (("compile_requests_use_cache", "cache_misses"), "miss"),
    (("compile_requests_use_cache", "cache_hits"), "hit")])
def test_a_compile_span_says_what_the_cache_did(monkeypatch, events, want):
    _sample(monkeypatch)
    for e in events:
        monitoring.record_event(CACHE + e)
    now = time.time()
    monitoring.record_event_time_span(COMPILE, now - 1.5, now,
                                      fun_name="jit(some_step)")
    monitoring.record_event_time_span(COMPILE, now, now + 0.5,
                                      fun_name="jit(some_step)")
    first, second = _setup_spans()
    assert first["name"] == second["name"] == "build.compile"
    assert first["fun"] == "some_step" and first["cache"] == want
    assert second["cache"] == "off"     # the first compile's answer is spent
    assert _build()["compile"] == {"n": 2, "s": pytest.approx(2.0)}


def test_build_spans_are_children_of_the_open_setup_span(monkeypatch):
    _sample(monkeypatch)

    def folded(x):
        return x.sum() * 2
    x = _operand(8)
    m0 = time.monotonic()
    with perfvars.setup_span("fold.compile", function="folded"):
        jax.block_until_ready(jax.jit(folded)(x))
    m1 = time.monotonic()
    spans = _setup_spans()
    (outer,) = [s for s in spans if s["name"] == "fold.compile"]
    mine = [s for s in spans if s.get("fun") == "folded"]
    assert [s["name"] for s in mine] == ["build." + p for p in PHASES]
    for s in mine:
        assert s["parent"] == outer["span"] and s["trace"] == outer["trace"]
        assert s["who"] == outer["who"] and s["status"] == "ok"
        # JAX's wall-clock stamps, moved onto the spans' monotonic clock
        assert m0 - 0.05 <= s["t0"] <= s["t1"] <= m1 + 0.05
    assert mine[2]["cache"] in ("off", "miss", "hit")
    assert "cache" not in mine[0] and "cache" not in mine[1]
    # arming_s is the top-level set-up spans' wall time, to the digit: a
    # build span adds nothing, under a set-up span or outside one
    arming = perfvars.snapshot()["arming_s"]
    assert arming == outer["t1"] - outer["t0"]
    jax.block_until_ready(jax.jit(folded)(_keep(jnp.ones(9))))
    assert perfvars.snapshot()["arming_s"] == arming
    assert any(s["name"] == "build.compile" and s["parent"] is None
               for s in _setup_spans())


def _keep(x):
    return jax.block_until_ready(x)


def test_spans_off_counts_and_publishes_nothing():
    x = _operand(6)
    jax.block_until_ready(jax.jit(lambda v: v - 1)(x))
    assert _counts("<lambda>") == (1, 1, 1)
    assert tracectx.drain() == []


def test_pvars_off_and_reset_leave_the_family_empty(monkeypatch):
    f = jax.jit(lambda v: v * 5)
    x = _operand(7)
    monkeypatch.setenv("TPU_MPI_PVARS", "0")
    config.load(refresh=True)
    assert not perfvars.enabled()
    jax.block_until_ready(f(x))
    perfvars.note_kernel_build("grouped_matmul_fwd")
    perfvars.note_step_fun("local_step")
    monitoring.record_event(CACHE + "cache_hits")
    monkeypatch.delenv("TPU_MPI_PVARS")
    config.load(refresh=True)
    assert _build() == {}
    jax.block_until_ready(f(_keep(jnp.ones(11))))
    perfvars.note_kernel_build("grouped_matmul_fwd")
    perfvars.note_step_fun("local_step")
    got = _build()
    assert got["kernels"] == {"grouped_matmul_fwd": 1}
    assert got["step"] == ["local_step"] and got["compile"]["n"] >= 1
    perfvars.reset()
    assert _build() == {}


def test_registering_twice_counts_once():
    assert perfvars.listen_builds() and perfvars.listen_builds()
    from jax._src import monitoring as registry
    for listeners, mine in (
            (registry.get_event_time_span_listeners(), perfvars._on_build_span),
            (registry.get_scalar_listeners(), perfvars._on_build_enter),
            (registry.get_event_listeners(), perfvars._on_cache_event),
            (registry.get_event_duration_listeners(),
             perfvars._on_cache_seconds)):
        assert listeners.count(mine) == 1
    x = _operand(10)
    jax.block_until_ready(jax.jit(lambda v: v / 3)(x))
    assert _counts("<lambda>") == (1, 1, 1)


def test_names_past_the_cap_are_summed_under_one_key(monkeypatch):
    monkeypatch.setattr(perfvars, "_BUILD_FUN_CAP", 3)
    now = time.time()
    for i in range(7):
        monitoring.record_event_time_span(COMPILE, now, now + 1.0,
                                          fun_name=f"jit(program_{i})")
    monitoring.record_event_time_span(COMPILE, now, now + 1.0,
                                      fun_name="jit_program_0")   # older JAX
    by_fun = _build()["by_fun"]
    assert sorted(by_fun) == [perfvars.BUILD_REST, "program_0", "program_1",
                              "program_2"]
    assert by_fun["program_0"]["compile"]["n"] == 2
    assert by_fun[perfvars.BUILD_REST]["compile"] == {"n": 4, "s": 4.0}
    assert _build()["compile"] == {"n": 8, "s": 8.0}


def test_the_pallas_import_is_a_span_and_no_arming(monkeypatch,
                                                   kernel_backend):
    _sample(monkeypatch)
    kernel_backend("interpret")
    choice.warm_kernel_imports()
    for t in threading.enumerate():
        if t.name == "tpu_mpi-pallas-import":
            t.join()
    (span,) = [s for s in _setup_spans() if s["name"] == "kernels.import"]
    assert span["parent"] is None and span["t1"] >= span["t0"]
    assert perfvars.snapshot()["arming_s"] == 0.0


def test_a_steps_kernels_are_counted_where_they_are_built(monkeypatch,
                                                          kernel_backend):
    """The count a wrapper notes equals the body traces that
    tests/test_grouped_matmul.py takes by patching the kernels' bodies: a
    four-layer step builds each distinct grouped kernel once (3 kinds x 2
    weight shapes), the forward pass alone two more, the expert counts
    nothing. The attention's forward kernel is built twice where it is
    differentiated, by the primal of its `custom_vjp` and by the forward
    rule; the program keeps one."""
    from tpu_mpi.models.transformer import (transformer_expert_counts,
                                            transformer_forward)
    kernel_backend("interpret")
    cfg = TransformerConfig(
        vocab=64, d_model=128, n_heads=2, n_layers=4, d_ff=256, max_seq=128,
        dtype=jnp.float32, norm_eps=1e-5, qk_norm=True, n_experts=4,
        experts_per_tok=2, router_aux_coef=0.01, tie_embeddings=False)
    bodies = {"gmm": 0, "tgmm": 0, "attn": 0}

    def counted(what, fn):
        def body(*args, **kwargs):
            bodies[what] += 1
            return fn(*args, **kwargs)
        return body
    monkeypatch.setattr(pk, "_gmm_kernel", counted("gmm", pk._gmm_kernel))
    monkeypatch.setattr(pk, "_tgmm_kernel", counted("tgmm", pk._tgmm_kernel))
    monkeypatch.setattr(pk, "_attn_fwd_kernel",
                        counted("attn", pk._attn_fwd_kernel))
    jitted = (pk._grouped_matmul_fn, pk._group_visits_fn,
              pk._causal_attention_fn)
    for cached in jitted:
        cached.cache_clear()    # jitted before the patches: traced afresh
    jax.clear_caches()
    perfvars.reset()

    mesh = xla.make_mesh({"dp": 1, "tp": 1, "sp": 1}, devices=jax.devices()[:1])
    step, _ = transformer_train_step(cfg, mesh, lr=0.1)
    params = jax.eval_shape(lambda k: transformer_init(k, cfg),
                            jax.random.key(0))
    tokens = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    text = step.lower(params, tokens, tokens).as_text()
    assert text.startswith("module @jit_local_step")    # named as it was
    got = _build()
    assert got["kernels"] == {
        "causal_attention_bwd": 1, "causal_attention_fwd": 2,
        "grouped_matmul_dlhs": 2, "grouped_matmul_drhs": 2,
        "grouped_matmul_fwd": 2}
    assert bodies == {"gmm": 4, "tgmm": 2, "attn": 2}
    assert got["step"] == ["local_step"]
    assert _counts("local_step") == (1, 1, 0)           # lowered, not compiled

    jax.jit(lambda p, t: transformer_forward(cfg, p, t)).lower(params, tokens)
    got = _build()["kernels"]
    assert got["grouped_matmul_fwd"] == 4 and got["causal_attention_fwd"] == 3
    assert bodies == {"gmm": 6, "tgmm": 2, "attn": 3}
    assert got["causal_attention_bwd"] == 1
    jax.jit(lambda p, t: transformer_expert_counts(cfg, p, t)).lower(
        params, tokens)
    assert _build()["kernels"] == got
    for cached in jitted:
        cached.cache_clear()    # they hold the counting bodies
