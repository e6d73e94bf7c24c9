"""The `lm_sambay_train_step` kind (a decoder-hybrid-decoder stack: Mamba-1
layers, window and full differential attention, gated memory units and
cross-attention over one layer's keys and values): the configuration file
against the catalog's keys and against the block the program is handed, the
FLOP, parameter and byte counts against hand counts, the scope rules on op
names, and the cell rehearsed at tiny size against its plain reference, with
two departures planted in the program and caught. Correctness only; nothing
is measured. Every entry is asserted by name and by no position, so a later
PR's append falsifies nothing here."""

import json
import os

import pytest

from yardstick import harness, lm_sambay_flops, sambay_scope_reduce
from test_generators import rehearse

CELL = "phi-4-mini-flash-reasoning-1c.sambay-step-b1s8192"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the published widths, by hand from the model's config.json, and the
# family's sizes the model's code fixes
WIDTHS = {"hidden_size": 2560, "intermediate_size": 10240,
          "num_attention_heads": 40, "num_key_value_heads": 20,
          "sliding_window": 512, "layer_norm_eps": 1e-05, "mb_per_layer": 2,
          "tie_word_embeddings": True, "hidden_act": "silu",
          "mlp_bias": False, "lm_head_bias": False, "embd_pdrop": 0,
          "resid_pdrop": 0, "mamba_d_state": 16, "mamba_d_conv": 4,
          "mamba_expand": 2, "mamba_dt_rank": 160}
NEW = ["sel_mixer_device_ms", "sel_scan_device_ms", "sel_scan_roofline",
       "gmu_device_ms", "diff_attn_device_ms", "diff_extra_device_ms",
       "shared_kv_attn_device_ms"]
# the accepted readers this cell shares with others, by their entries' names
# (PR 50 folded the tags that named a cell into the readers' cell lists)
SHARED = ["step_device_ms", "train_mfu", "device_idle_share.train",
          "fused_attn_share", "ssm_conv_device_ms", "dense_ffn_device_ms",
          "kinds_head_loss_device_ms", "embed_device_ms",
          "row_sum_product_share", "step_build_s", "kernel_traces",
          "build_trace_s", "build_lower_s", "build_compile_s",
          "build_cache_misses"]


@pytest.fixture(scope="module")
def manifest():
    return harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))


def agrees(conf: dict) -> None:
    """The block handed to the program says what the published keys say, and
    lays the layers out by the model's own rule."""
    m, n = conf["model"], conf["num_hidden_layers"]
    assert n % 4 == 0 and m["n_layers"] == n == len(m["mixer_kinds"])
    kinds = harness.load_module(
        os.path.join(harness.HERE, "reference", conf["kind"] + ".py"),
        "ys_reference_" + conf["kind"]).kinds(conf)
    assert m["mixer_kinds"] == [
        {"memory": "mamba", "window": "attention", "full": "attention"}.get(
            k, k) for k in kinds]
    assert m["attn_windows"] == [
        conf["sliding_window"] if k == "window" else 0 for k in kinds]
    assert m["memory_from"] == kinds.index("memory") == n // 2
    assert m["kv_from"] == kinds.index("full") == n // 2 + 1
    assert m["d_model"] == conf["hidden_size"]
    assert m["d_ff"] == conf["intermediate_size"]
    assert (m["n_heads"], m["n_kv_heads"]) == (
        conf["num_attention_heads"], conf["num_key_value_heads"])
    assert m["n_heads"] % 2 == 0 and m["n_kv_heads"] % 2 == 0
    assert m["d_head"] == conf["hidden_size"] // conf["num_attention_heads"]
    assert (m["ssm_state"], m["ssm_conv"], m["ssm_expand"],
            m["ssm_dt_rank"]) == (
        conf["mamba_d_state"], conf["mamba_d_conv"], conf["mamba_expand"],
        conf["mamba_dt_rank"])
    assert m["ssm_expand"] * m["d_model"] == 2 * conf["hidden_size"]  # d_inner
    assert m["diff_attn"] and m["attn_bias"]
    assert m["norm_kind"] == "layer" and m["norm_eps"] == conf["layer_norm_eps"]
    assert m["dense_gated"] and conf["hidden_act"] == "silu"
    assert not conf["mlp_bias"] and not conf["lm_head_bias"]
    assert not {"n_experts", "ffn_kinds", "experts_held"} & set(m)
    assert m["vocab"] == conf["vocab_size"]
    assert m["tie_embeddings"] is conf["tie_word_embeddings"] is True
    assert conf["model_type"] == "phi4flash" and conf["mb_per_layer"] == 2
    assert (m["ssm_heads"], m["ssm_head_dim"]) == (0, 0)    # Mamba-1: none


def test_the_configuration_keeps_every_published_width(manifest):
    cell = harness.Cell(manifest, CELL)
    conf = cell.config
    assert conf["kind"] == cell.traffic["kind"] == "lm_sambay_train_step"
    for key, want in WIDTHS.items():
        assert conf[key] == want, key
    assert conf["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert conf["num_hidden_layers"] == 16 and conf["vocab_size"] == 66688
    assert 3 * 66688 == 200064 and 66688 % 128 == 0
    assert "32" in conf["reduced_why"] and "200064" in conf["reduced_why"]
    kinds = cell.reference().kinds(conf)
    assert [kinds.count(k) for k in ("mamba", "memory", "window", "full",
                                     "gmu", "cross")] == [4, 1, 4, 1, 3, 3]
    assert conf["model"]["remat_layers"] == ["ffn"] * 16
    assert conf["model"]["dtype"] == "bfloat16"
    assert (cell.traffic["batch"], cell.traffic["seq"], cell.traffic["pool"],
            cell.traffic["block_steps"]) == (1, 8192, 16, 1)
    assert set(conf["assumed"]) >= {
        "mamba_sizes", "ssm_init", "lambda_init", "biases", "head_pairs",
        "ffn_halves", "memory", "sequence_length", "optimizer", "dropout",
        "weights", "tokens"}
    assert conf["compare_steps"] == 3 and conf["mesh"] == {
        "dp": 1, "tp": 1, "sp": 1}
    for key in ("loss_tolerance", "logits_tolerance", "update_tolerance",
                "model"):
        assert len(conf[key + "_why"]) > 200, key   # a reason and readings
    entry = {c["name"]: c for c in manifest["configs"]}[conf["name"]]
    assert entry["source"] == conf["source"]
    assert entry["reduced"] == conf["reduced"]
    assert entry["file"] == \
        "yardstick/configs/phi-4-mini-flash-reasoning-1c.json"
    agrees(conf)
    agrees(harness.Cell(manifest, CELL, rehearse=True).config)
    assert cell.entry["chips"] == 1
    assert CELL in next(m for m in manifest["end_to_end"]
                        if m["name"] == "train_tokens_per_s")["workloads"]


def test_the_configuration_against_the_catalog(manifest):
    """Every key of the catalog row's `config` is in the file under the same
    key with the same value, but for the depth and the vocabulary."""
    if not os.path.isfile(CATALOG):
        pytest.skip("the model catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Phi-4-mini-flash-reasoning")
    conf = harness.Cell(manifest, CELL).config
    assert conf["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if conf.get(k) != v}
    assert changed == {"num_hidden_layers", "vocab_size"}
    assert row["config"]["num_hidden_layers"] == 32
    assert row["config"]["vocab_size"] == 200064


def test_the_cell_reports_what_the_issue_names(manifest):
    cell = harness.Cell(manifest, CELL)
    by_name = {m["name"]: m for m in cell.per_layer}
    assert len(by_name) == len(cell.per_layer)
    assert set(NEW + SHARED) | {"compiles_in_window", "backend_start_s"} \
        <= set(by_name)
    for name in NEW + SHARED:
        spec = by_name[name]
        assert CELL in spec["workloads"], name
        assert spec["moves"] == ("setup_s" if name.startswith(
            ("step_build_s", "kernel_traces", "build_"))
            else "train_tokens_per_s"), name
    assert by_name["sel_scan_roofline"]["unit"] == "%"
    assert by_name["sel_scan_roofline"]["better"] == "higher"
    for _spec, mod in cell.readers():
        assert hasattr(mod, "read")
    # one four-chip cell of nine
    chips = [w["chips"] for w in manifest["workloads"]]
    assert chips.count(4) == 1 and len(chips) >= 9


def test_parameters_flops_and_bytes_against_a_hand_count(manifest):
    model = harness.Cell(manifest, CELL).config["model"]
    t, d, f, inner = 8192, 2560, 10240, 5120
    mamba = d * 2 * inner + inner * (160 + 32) + 160 * inner + inner * d
    mamba_rest = 4 * inner + inner + inner + inner * 16 + inner
    attn = d * (2560 + 2 * 1280) + 2560 * d
    attn_rest = 2560 + 2 * 1280 + d + 4 * 64 + 128
    cross = 2 * d * 2560
    cross_rest = 2560 + d + 4 * 64 + 128
    gmu = 2 * d * inner
    ffn, norms = 3 * d * f, 4 * d
    assert mamba + mamba_rest == 41_241_600
    assert attn + attn_rest == 19_668_864
    assert cross + cross_rest == 13_112_704
    assert gmu == 26_214_400 and ffn == 78_643_200 and norms == 10_240
    for mixer, want in (("mamba", mamba), ("window", attn), ("full", attn),
                        ("cross", cross), ("gmu", gmu)):
        assert lm_sambay_flops.mixer_matrix_params(model, mixer) == want
    layers = 5 * (mamba + mamba_rest) + 5 * (attn + attn_rest) + 3 * gmu \
        + 3 * (cross + cross_rest) + 16 * (ffn + norms)
    assert layers == 1_680_988_672
    assert lm_sambay_flops.params_count(model) \
        == layers + 66688 * d + 2 * d == 1_851_715_072
    # forward: every matrix once a token; 40 heads' scores (64 wide) and
    # values (128 wide) under the mask; the scan as the recurrence
    full = t * (t + 1) // 2
    window = 512 * 513 // 2 + (t - 512) * 512
    assert lm_sambay_flops.score_pairs(t, 0) == full
    assert lm_sambay_flops.score_pairs(t, 512) == window
    scores = 40 * (2 * 64 + 2 * 128)
    scan = 2 * 2 * t * inner * 16
    matrices = 5 * mamba + 5 * attn + 3 * gmu + 3 * cross + 16 * ffn
    fwd = 2 * t * (matrices + 66688 * d) + scores * (4 * full + 4 * window) \
        + 5 * scan
    assert lm_sambay_flops.flops_per_step(model, 1, t) == 3 * fwd
    assert 97.5e12 < 3 * fwd < 98.5e12          # ISSUE 41: about 99 T a step
    assert 82.5e12 < 3 * 2 * t * matrices < 82.7e12             # the layers
    assert 0.08 < 3 * 2 * t * 66688 * d / (3 * fwd) < 0.09      # the head
    assert 3 * 5 * scan / (3 * fwd) < 0.001                     # the scans
    # the scan's least bytes a layer: x, dt, B, C in and y out, in bfloat16
    inputs = 2 * inner + 2 * 16
    least = lm_sambay_flops.scan_least_bytes(model, 1, t, 2)
    assert least == {"fwd": 2 * t * (inputs + inner),
                     "bwd": 2 * t * (inputs + inner + inputs)}
    assert least == {"fwd": 252_182_528, "bwd": 420_478_976}    # 0.82 ms
    assert lm_sambay_flops.layer_mixers(model) == [
        "mamba", "window"] * 4 + ["mamba", "full"] + ["gmu", "cross"] * 3


def test_the_counts_are_the_programs_tree(manifest):
    """`params_count` against `transformer_init`'s own tree at the published
    sizes (shapes only)."""
    import jax
    import jax.numpy as jnp
    from tpu_mpi.models.transformer import TransformerConfig, transformer_init
    model = harness.Cell(manifest, CELL).config["model"]
    fields = dict(model, max_seq=8192, dtype=jnp.dtype(model["dtype"]))
    shapes = jax.eval_shape(
        lambda k: transformer_init(k, TransformerConfig(**fields)),
        jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) \
        == lm_sambay_flops.params_count(model)


MIXERS = ["mamba", "window"] * 2 + ["mamba", "full", "gmu", "cross"]


@pytest.mark.parametrize("op_name, scope, diff", [
    ("jit(local_step)/jvp(layer_2)/jit(block)/mixer/in_proj/dot_general",
     "in_proj", False),
    ("jit(local_step)/transpose(jvp(layer_0))/jit(block)/mixer/conv/mul",
     "conv", False),
    ("jit(local_step)/jvp(layer_4)/jit(block)/mixer/x_proj/softplus",
     "x_proj", False),
    ("jit(local_step)/transpose(jvp(layer_4))/jit(block)/mixer/scan/"
     "checkpoint/rematted_computation/while/body/exp", "scan", False),
    ("jit(local_step)/jvp(layer_0)/jit(block)/mixer/gate/mul", "gate", False),
    ("jit(local_step)/transpose(jvp(layer_2))/jit(block)/mixer/out_proj/"
     "dot_general", "out_proj", False),
    ("jit(local_step)/jvp(layer_2)/jit(block)/mixer/add", "mamba_rest", False),
    ("jit(local_step)/jvp(layer_6)/jit(block)/mixer/gmu/dot_general", "gmu",
     False),
    ("jit(local_step)/jvp(layer_6)/jit(block)/mixer/reduce_sum", "gmu_rest",
     False),
    ("jit(local_step)/jvp(layer_1)/jit(block)/attn/jit(attend)/"
     "causal_attention_fwd/pallas_call", "attn_window", False),
    ("jit(local_step)/transpose(jvp(layer_5))/jit(block)/attn/diff/mul",
     "attn_full", True),
    ("jit(local_step)/jvp(layer_7))/jit(block)/attn/diff/rsqrt", "attn_cross",
     True),
    ("jit(local_step)/jvp(layer_7)/jit(block)/attn/dot_general", "attn_cross",
     False),
    ("jit(local_step)/jvp(layer_0)/jit(block)/mlp/dense/dot_general", "other",
     False),
    ("jit(local_step)/jvp(head_loss)/dot_general", "other", False),
    ("reduce_sum", "other", False),
    ("params['layers'][2]['w_ssm_in']", "in_proj", False),
    ("params['layers'][0]['w_ssm_x']", "x_proj", False),
    ("params['layers'][4]['a_log']", "scan", False),
    ("params['layers'][6]['w_gmu_out']", "gmu", False),
    ("params['layers'][5]['w_k']", "attn_full", False),
    ("params['layers'][7]['w_proj']", "attn_cross", False),
    ("params['layers'][3]['w_gate']", "other", False),
    ("params['embed']", "other", False),
])
def test_scope_rules(op_name, scope, diff):
    assert sambay_scope_reduce.scope_of(op_name, MIXERS) == (scope, diff)
    assert scope in sambay_scope_reduce.SCOPES


def test_the_cell_matches_its_plain_reference():
    run = rehearse(CELL, seconds=0.5)
    r = run.results
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 2
    assert set(r["metrics"]) == {"train_tokens_per_s"}
    assert run.values["compiles_in_window"] == 0
    assert run.facts["scan"]["layers"] == 3         # N = 8 in the rehearsal
    assert run.facts["flops_per_step"] > 0
    # every kind was traced, the scan in its chunked form, the side values
    # were read, and the program chose its attention's lowering
    begin = run.counters["begin"]
    for kind in ("mamba", "gmu", "cross", "attention"):
        assert begin["mixer_kinds"][kind] >= 1, kind
    assert begin["sel_scan_lowerings"]["chunked"] >= 1
    assert not begin["sel_scan_lowerings"]["padded"]
    assert begin["side_values"]["memory"] >= 1 <= begin["side_values"]["kv"]
    assert begin["attn_kinds"]["diff"] == "plain"
    # no trace on the CPU: the scope readers report nothing and do not raise
    for name, value in run.values.items():
        if "device_ms" in name or "roofline" in name:
            assert value is None, name


@pytest.fixture
def fresh_traces():
    """A planted departure must reach the trace: the layer kinds' jitted
    functions are dropped before and after."""
    from tpu_mpi.models import transformer
    transformer._block_traced_once.cache_clear()
    yield transformer
    transformer._block_traced_once.cache_clear()


def test_a_wrong_lambda_init_is_not_correct(monkeypatch, fresh_traces):
    """The program with `lambda_init` of the NEXT layer's depth."""
    import jax.numpy as jnp
    monkeypatch.setattr(
        fresh_traces, "lambda_init",
        lambda depth: 0.8 - 0.6 * jnp.exp(-0.3 * (depth + 1.0)))
    assert not rehearse(CELL, seconds=0.2).results["correct"]


def test_a_memory_taken_after_the_gate_is_not_correct(monkeypatch,
                                                      fresh_traces):
    """The program with the memory layer's GATED scan output as the memory."""
    import jax
    tf = fresh_traces
    honest = tf._mamba_mixer

    def gated_memory(cfg, layer, x):
        out, wrote = honest(cfg, layer, x)
        z = (tf._norm(cfg, x, layer, "ln1")
             @ layer["w_ssm_in"])[..., cfg.mamba_inner:]
        return out, {"memory": wrote["memory"] * jax.nn.silu(z)}
    monkeypatch.setattr(tf, "_mamba_mixer", gated_memory)
    assert not rehearse(CELL, seconds=0.2).results["correct"]


def test_a_wrong_loss_or_wrong_logits_are_not_correct(monkeypatch):
    manifest = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    ref = harness.Cell(manifest, CELL).reference()
    honest = ref.make_loss_from

    def off(model, d_loss, d_logits):
        def loss_from(*a, **kw):
            loss, logits = honest(model)(*a, **kw)
            return loss + d_loss, None if logits is None \
                else logits * (1.0 + d_logits)
        return loss_from
    monkeypatch.setattr(ref, "make_loss_from", lambda m: off(m, 1e-3, 0.0))
    assert not rehearse(CELL, seconds=0.2).results["correct"]
    monkeypatch.setattr(ref, "make_loss_from", lambda m: off(m, 0.0, 1e-3))
    assert not rehearse(CELL, seconds=0.2).results["correct"]


def test_a_wrong_update_is_not_correct(monkeypatch):
    """The timed executable at 1.2 x its learning rate misses the update's
    tolerance (the loss and the logits cannot see it)."""
    import jax
    from tpu_mpi.models import transformer
    honest = transformer.transformer_train_step

    def other_lr(*a, **kw):
        step, specs = honest(*a, **kw)

        class Lowered:
            def compile(self):
                def bad(p, tok, lab):
                    new, loss = step(p, tok, lab)
                    return jax.tree.map(lambda a, b: b + 0.2 * (b - a),
                                        p, new), loss
                return bad

        class Step:
            lower = staticmethod(lambda *args: Lowered())
        return Step, specs
    monkeypatch.setattr(
        transformer, "transformer_train_step",
        lambda cfg, mesh, lr, donate: other_lr(cfg, mesh, lr=lr, donate=False))
    assert not rehearse(CELL, seconds=0.2).results["correct"]


LOOPS_HLO = """HloModule jit_local_step, is_scheduled=true

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %exp.1 = f32[8]{0} exponential(%param_0)
}

%inner_body (p.1: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p.1 = (s32[], f32[8]{0}) parameter(0)
  %fusion.7 = f32[8]{0} fusion(%p.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(local_step)/jvp(layer_0)/mixer/scan/while/body/while/body/exp"}
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%p.1, %fusion.7)
}

%inner_cond (p.2: (s32[], f32[8])) -> pred[] {
  %p.2 = (s32[], f32[8]{0}) parameter(0)
  ROOT %compare.1 = pred[] compare(%p.2, %p.2), direction=LT
}

%outer_body (p.3: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p.3 = (s32[], f32[8]{0}) parameter(0)
  %fusion.8 = f32[8]{0} fusion(%p.3), kind=kLoop, calls=%fused_computation.1
  ROOT %while.9 = (s32[], f32[8]{0}) while(%p.3), condition=%inner_cond, body=%inner_body, metadata={op_name="jit(local_step)/jvp(layer_0)/mixer/scan/while/body/while"}
}

%outer_cond (p.4: (s32[], f32[8])) -> pred[] {
  %p.4 = (s32[], f32[8]{0}) parameter(0)
  ROOT %compare.2 = pred[] compare(%p.4, %p.4), direction=LT
}

ENTRY %main.1 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0} parameter(0)
  %while_fusion.3 = f32[8]{0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.1
  %while.4 = (s32[], f32[8]{0}) while(%Arg_0.1), condition=%outer_cond, body=%outer_body, metadata={op_name="jit(local_step)/jvp(layer_0)/mixer/scan/while"}
  ROOT %get-tuple-element.5 = f32[8]{0} get-tuple-element(%while.4), index=1
}
"""


def test_a_loop_is_its_outermost_event():
    """A `while` instruction's event spans its body's ops and the loop's
    control: the reducer takes the outermost loop's event for the loop and
    leaves out what is nested in it, a loop in a loop too."""
    nested = sambay_scope_reduce.nested_in_loops(LOOPS_HLO)
    assert {"fusion.7", "fusion.8", "while.9", "compare.1", "compare.2",
            "tuple.1"} <= nested
    assert not {"while.4", "while_fusion.3", "get-tuple-element.5",
                "Arg_0.1"} & nested
    names = dict(sambay_scope_reduce.scope_reduce.INSTRUCTION.findall(
        LOOPS_HLO))
    assert sambay_scope_reduce.scope_of(names["while.4"], MIXERS) == (
        "scan", False)
