"""The `lm_gdn_train_step` kind (linear attention with a gated delta rule three
layers in four, gated softmax attention, held experts beside a gated shared
expert): the configuration file against the catalog's keys and against the
block the program is handed, the parameter, FLOP and byte counts against hand
counts, the scope rules on op names, and the cell rehearsed at tiny size
against its plain reference, with departures planted and caught.
Correctness only; nothing is measured. Every entry is asserted by name and as
a subset, never by position nor as an exact list, so a later PR's append
falsifies nothing here."""

import json
import os

import pytest

from yardstick import gdn_scope_reduce, harness, lm_gdn_flops
from test_generators import rehearse

CELL = "qwen3-next-80b-a3b-1c.gdn-step-b1s8192"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
# the published widths, by hand from the model's config.json
WIDTHS = {"hidden_size": 2048, "head_dim": 256, "num_attention_heads": 16,
          "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
          "rope_theta": 10000000, "linear_num_key_heads": 16,
          "linear_num_value_heads": 32, "linear_key_head_dim": 128,
          "linear_value_head_dim": 128, "linear_conv_kernel_dim": 4,
          "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
          "num_experts_per_tok": 10, "norm_topk_prob": True,
          "full_attention_interval": 4, "decoder_sparse_step": 1,
          "mlp_only_layers": [], "rms_norm_eps": 1e-06, "hidden_act": "silu",
          "tie_word_embeddings": False, "intermediate_size": 5120,
          "router_num_experts": 512}
# `per_layer` holds at most 128 entries and the benchmark had 123: this cell
# brings five of its own, and reads the accepted readers under the accepted
# entries of the same `moves`, its name appended to their `workloads`
NEW = ["gdn_mixer_device_ms", "gdn_scan_device_ms", "gdn_scan_roofline",
       "gated_attn_device_ms", "delta_chunked_share"]
JOINED = ["step_device_ms", "train_mfu", "device_idle_share.train",
          "fused_attn_share", "grouped_matmul_share", "row_sum_product_share",
          "ssm_conv_device_ms", "held_moe_device_ms",
          "held_dispatch_device_ms", "shared_expert_device_ms",
          "held_experts_roofline", "held_slot_share", "expert_rows_fill",
          "kinds_head_loss_device_ms", "embed_device_ms", "step_build_s",
          "kernel_traces", "build_trace_s", "build_lower_s",
          "build_compile_s", "build_cache_misses", "blocked_head_share"]
# the train cells accepted before this one
TRAIN_CELLS = [
    "flagship-d1024-1c.step-b8s1024", "olmoe-1b-7b-1c.lm-step-b2s4096",
    "k-exaone-236b-a23b-1c.lm-step-b1s8192",
    "openpangu-ultra-moe-718b-1c.lm-step-b1s4096",
    "granite-4.0-h-micro-1c.ssm-step-b1s8192",
    "phi-4-mini-flash-reasoning-1c.sambay-step-b1s8192"]


@pytest.fixture(scope="module")
def manifest():
    return harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))


def agrees(conf: dict) -> None:
    """The block handed to the program says what the published keys say, and
    lays the layers out by the model's own rule."""
    m, n = conf["model"], conf["num_hidden_layers"]
    assert n % conf["full_attention_interval"] == 0
    assert m["n_layers"] == n == len(m["mixer_kinds"]) == len(m["remat_layers"])
    kinds = harness.load_module(
        os.path.join(harness.HERE, "reference", conf["kind"] + ".py"),
        "ys_reference_" + conf["kind"]).kinds(conf)
    assert m["mixer_kinds"] == [
        {"linear": "gdn", "full": "attention"}[k] for k in kinds]
    assert m["d_model"] == conf["hidden_size"]
    assert (m["n_heads"], m["n_kv_heads"], m["d_head"]) == (
        conf["num_attention_heads"], conf["num_key_value_heads"],
        conf["head_dim"])
    assert m["rotary_dim"] == conf["head_dim"] * conf["partial_rotary_factor"]
    assert m["rope_theta"] == conf["rope_theta"]
    assert (m["gdn_key_heads"], m["gdn_key_dim"], m["gdn_value_heads"],
            m["gdn_value_dim"], m["gdn_conv"]) == (
        conf["linear_num_key_heads"], conf["linear_key_head_dim"],
        conf["linear_num_value_heads"], conf["linear_value_head_dim"],
        conf["linear_conv_kernel_dim"])
    assert m["d_ff"] == conf["moe_intermediate_size"]
    assert m["n_shared_experts"] * m["d_ff"] == \
        conf["shared_expert_intermediate_size"]
    assert m["n_experts"] == conf["router_num_experts"]
    assert m["experts_held"] == [conf["held_experts_first"],
                                 conf["num_experts"]]
    assert m["experts_per_tok"] == conf["num_experts_per_tok"]
    assert m["router_score"] == "softmax"
    assert m["router_renorm"] is conf["norm_topk_prob"] is True
    assert m["norm_eps"] == conf["rms_norm_eps"]
    assert m["qk_norm_heads"] and m["attn_out_gate"] and m["norm_unit_offset"]
    assert m["shared_expert_gate"]
    assert m["vocab"] == conf["vocab_size"]
    assert m["tie_embeddings"] is conf["tie_word_embeddings"] is False
    assert not {"ffn_kinds", "attn_windows", "router_scale"} & set(m)
    assert conf["model_type"] == "qwen3_next" and conf["hidden_act"] == "silu"


def test_the_configuration_keeps_every_published_width(manifest):
    cell = harness.Cell(manifest, CELL)
    conf = cell.config
    assert conf["kind"] == cell.traffic["kind"] == "lm_gdn_train_step"
    for key, want in WIDTHS.items():
        assert conf[key] == want, key
    assert conf["reduced"] == REDUCED
    assert (conf["num_hidden_layers"], conf["num_experts"],
            conf["vocab_size"]) == (8, 64, 18992)
    assert 8 * 18992 == 151936 and 8 * 64 == 512
    assert 18992 % 128 and 18992 == 2 ** 4 * 1187   # no multiple of 128
    for word in ("48", "512", "151936"):
        assert word in conf["reduced_why"], word
    assert "8 chips share each layer" in conf["deployment"]
    assert "160" in conf["deployment"]
    kinds = cell.reference().kinds(conf)
    assert kinds == ["linear", "linear", "linear", "full"] * 2
    assert conf["model"]["dtype"] == "bfloat16"
    assert conf["model"]["gdn_chunk"] == 64         # the model's own chunk
    assert (cell.traffic["batch"], cell.traffic["seq"], cell.traffic["pool"],
            cell.traffic["block_steps"]) == (1, 8192, 16, 2)
    assert set(conf["assumed"]) >= {
        "column_order", "delta_init", "norm_scales", "multi_token_module",
        "auxiliary_loss", "sequence_length", "optimizer", "weights", "tokens"}
    assert conf["compare_steps"] == 3 and conf["mesh"] == {
        "dp": 1, "tp": 1, "sp": 1}
    for key in ("loss_tolerance", "logits_tolerance", "update_tolerance",
                "model"):
        assert len(conf[key + "_why"]) > 200, key   # a reason and readings
    assert len(conf["guarantees"]) > 200
    entry = {c["name"]: c for c in manifest["configs"]}[conf["name"]]
    assert entry["source"] == conf["source"]
    assert entry["reduced"] == conf["reduced"]
    assert entry["file"] == "yardstick/configs/qwen3-next-80b-a3b-1c.json"
    agrees(conf)
    agrees(harness.Cell(manifest, CELL, rehearse=True).config)
    assert cell.entry["chips"] == 1


def test_the_configuration_against_the_catalog(manifest):
    """Every key of the catalog row's `config` is in the file under the same
    key with the same value, but for the depth, the experts held and the
    vocabulary rows held."""
    if not os.path.isfile(CATALOG):
        pytest.skip("the model catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    conf = harness.Cell(manifest, CELL).config
    assert conf["source"] == row["source_url"]
    assert set(row["config"]) <= set(conf)
    changed = {k for k, v in row["config"].items() if conf[k] != v}
    assert changed == set(REDUCED)
    assert (row["config"]["num_hidden_layers"], row["config"]["num_experts"],
            row["config"]["vocab_size"]) == (48, 512, 151936)


def test_the_cell_reports_what_the_issue_names(manifest):
    cell = harness.Cell(manifest, CELL)
    by_name = {m["name"]: m for m in cell.per_layer}
    assert set(NEW + JOINED) | {"compiles_in_window",
                                "backend_start_s"} <= set(by_name)
    assert len(manifest["per_layer"]) <= 128
    for name in NEW:
        assert CELL in by_name[name]["workloads"], name
    for name in JOINED:         # joined the cells the entry had
        assert {CELL} < set(by_name[name]["workloads"]), name
    for name in NEW + JOINED:
        assert by_name[name]["moves"] == ("setup_s" if name.startswith(
            ("step_build_s", "kernel_traces", "build_"))
            else "train_tokens_per_s"), name
    for name in ("gdn_scan_roofline", "held_experts_roofline", "train_mfu",
                 "delta_chunked_share"):
        assert (by_name[name]["unit"], by_name[name]["better"]) == (
            "%", "higher"), name
    assert by_name["delta_chunked_share"]["source"] == "program_counter"
    for _spec, mod in cell.readers():
        assert hasattr(mod, "read")
    # the cell reports the rate, with the accepted train cells
    for metric in ("train_tokens_per_s", "blocked_head_share"):
        (spec,) = [m for m in manifest["end_to_end"] + manifest["per_layer"]
                   if m["name"] == metric]
        assert set(TRAIN_CELLS) | {CELL} <= set(spec["workloads"]), metric
    (head,) = [m for m in manifest["per_layer"]
               if m["name"] == "blocked_head_share"]
    assert {k: v for k, v in head.items() if k != "workloads"} == {
        "name": "blocked_head_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "train step",
        "moves": "train_tokens_per_s"}
    for w in manifest["workloads"]:         # and no cell that does not train
        names = [m["name"] for m in harness.Cell(manifest, w["name"]).per_layer]
        assert ("blocked_head_share" in names) == (
            w["name"] in head["workloads"]), w["name"]
        assert len(set(names)) == len(names)
    # still one four-chip cell
    assert [w["chips"] for w in manifest["workloads"]].count(4) == 1


def test_the_six_build_entries_stand(manifest):
    """PR 34's six entries (`test_build_metrics.py`) with this kind's first
    four train cells among their cells."""
    osu = ["osu-allreduce-4r1c.large-reuse", "osu-allreduce-4r1c.small-reuse",
           "osu-allreduce-4r4c.large-reuse"]
    runtime, step = "launcher and runtime", "train step"
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name, (unit, source, layer, cells) in {
            "build_trace_s": ("s", "program_span", runtime, osu),
            "build_lower_s": ("s", "program_span", runtime, osu),
            "build_compile_s": ("s", "program_span", runtime, osu),
            "build_cache_misses": ("count", "program_counter", runtime, osu),
            "step_build_s": ("s", "program_span", step, []),
            "kernel_traces": ("count", "program_counter", step, [])}.items():
        spec = dict(by_name[name])
        assert set(cells + TRAIN_CELLS[:4]) <= set(spec.pop("workloads")), name
        assert spec == {"name": name, "unit": unit, "better": "lower",
                        "source": source, "layer": layer,
                        "moves": "setup_s"}, name
    names = [m["name"] for m in manifest["per_layer"]]
    assert len(set(names)) == len(names)
    setup = next(m for m in manifest["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup         # every cell reports it


def test_parameters_flops_and_bytes_against_a_hand_count(manifest):
    """ISSUE 45's count, by hand."""
    model = harness.Cell(manifest, CELL).config["model"]
    t, d, f = 8192, 2048, 512
    gdn = d * 12288 + d * 64 + 4096 * d
    gdn_rest = 8192 * 4 + 32 + 32 + 128
    attn = d * 8192 + 2 * d * 512 + 4096 * d
    attn_rest = 512
    experts = d * 512 + 3 * d * f + d + 64 * 3 * d * f
    assert gdn + gdn_rest == 33_718_464
    assert attn + attn_rest == 27_263_488
    assert experts == 205_522_944
    assert lm_gdn_flops.mixer_matrix_params(model, "gdn") == gdn
    assert lm_gdn_flops.mixer_other_params(model, "gdn") == gdn_rest
    assert lm_gdn_flops.mixer_matrix_params(model, "full") == attn
    assert lm_gdn_flops.mixer_other_params(model, "full") == attn_rest
    assert lm_gdn_flops.expert_half_matrix_params(model) == experts
    layers = 6 * (gdn + gdn_rest + experts + 2 * d) \
        + 2 * (attn + attn_rest + experts + 2 * d)
    assert layers == 6 * 239_245_504 + 2 * 232_790_528 == 1_901_054_080
    assert lm_gdn_flops.params_count(model) \
        == layers + 2 * 18992 * d + d == 1_978_847_360
    assert lm_gdn_flops.layer_mixers(model) == ["gdn", "gdn", "gdn",
                                                "full"] * 2
    # forward: every matrix once a token; the delta scan as the recurrence
    # (three products with a [128 x 128] state a value head and token); 16
    # heads' scores and values over the whole [t x t]; 160 rows an expert
    rows = t * 10 * 64 // 512
    assert rows == 64 * 160
    head = 2 * t * d * 18992
    gdn_fwd = 2 * t * gdn + 3 * 2 * t * 32 * 128 * 128
    attn_fwd = 2 * t * attn + 2 * 2 * t * t * 16 * 256
    half = 2 * t * d * 512 + 2 * rows * 3 * d * f + 2 * t * 3 * d * f \
        + 2 * t * d
    fwd = head + 6 * gdn_fwd + 2 * attn_fwd + 8 * half
    assert lm_gdn_flops.flops_per_step(model, 1, t) == 3 * fwd
    assert lm_gdn_flops.flops_per_step(model, 1, t, held_rows=rows) == 3 * fwd
    assert 24.5e12 < 3 * fwd < 25.0e12              # ISSUE 45: about 25 T
    assert 0.07 < head / fwd < 0.08                 # the head
    assert 0.70e12 < gdn_fwd + half < 0.75e12       # ISSUE 45: 0.74 a layer
    assert 1.65e12 < attn_fwd + half < 1.70e12      # ISSUE 45: 1.68
    # the scan alone, one layer: the chunked form's matrix FLOPs at chunk 64
    chunks, length = t // 64, 64
    lower = length * (length - 1) // 2
    scan = chunks * (16 * 2 * 2 * length * length * 128
                     + 32 * (2 * lower * 256 + 3 * 2 * length * 128 * 128
                             + 2 * length * length * 128))
    assert lm_gdn_flops.scan_chunked_flops(model, 1, t) == {
        "fwd": scan, "bwd": 2 * scan}
    assert scan == 38_587_596_800                   # 0.59 ms with backward
    # its least bytes: q, k, v in bfloat16, g and beta float32, o out
    inputs = 2 * (2048 + 2048 + 4096) + 4 * 2 * 32
    least = lm_gdn_flops.scan_least_bytes(model, 1, t, 2)
    assert least == {"fwd": t * (inputs + 2 * 4096),
                     "bwd": t * (2 * inputs + 2 * 4096)}
    assert least == {"fwd": 203_423_744, "bwd": 339_738_624}   # 0.66 ms


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
        == lm_gdn_flops.params_count(model) == 1_978_847_360


MIXERS = ["gdn", "gdn", "gdn", "full"] * 2


@pytest.mark.parametrize("op_name, scope, extra", [
    ("jit(local_step)/jvp(layer_2)/jit(block)/mixer/in_proj/dot_general",
     "in_proj", False),
    ("jit(local_step)/jvp(layer_0)/jit(block)/mixer/checkpoint/conv/mul",
     "conv", False),
    ("jit(local_step)/transpose(jvp(layer_0))/jit(block)/mixer/checkpoint/"
     "rematted_computation/prep/rsqrt", "prep", False),
    ("jit(local_step)/transpose(jvp(layer_4))/jit(block)/mixer/scan/mixer/"
     "scan/checkpoint/rematted_computation/while/body/dot_general", "scan",
     False),
    ("jit(local_step)/jvp(layer_6)/jit(block)/mixer/scan/while", "scan",
     False),
    ("jit(local_step)/jvp(layer_5)/jit(block)/mixer/gate_norm/mul",
     "gate_norm", False),
    ("jit(local_step)/transpose(jvp(layer_1))/jit(block)/mixer/out_proj/"
     "dot_general", "out_proj", False),
    ("jit(local_step)/jvp(layer_1)/jit(block)/mixer/add", "gdn_rest", False),
    ("jit(local_step)/jvp(layer_3)/jit(block)/attn/jit(attend)/"
     "causal_attention_fwd/pallas_call", "attn", False),
    ("jit(local_step)/jvp(layer_3)/jit(block)/attn/qk_norm/checkpoint/rsqrt",
     "attn", True),
    ("jit(local_step)/transpose(jvp(layer_7))/jit(block)/attn/out_gate/"
     "checkpoint/rematted_computation/logistic", "attn", True),
    ("jit(local_step)/jvp(layer_7)/jit(block)/attn/dot_general", "attn",
     False),
    # a delta layer has no `attn`, an attention layer no `mixer`
    ("jit(local_step)/jvp(layer_0)/jit(block)/attn/dot_general", "other",
     False),
    ("jit(local_step)/jvp(layer_0)/jit(block)/mlp/shared/shared_gate/"
     "logistic", "other", False),
    ("jit(local_step)/jvp(head_loss)/dot_general", "other", False),
    ("reduce_sum", "other", False),
    ("params['layers'][2]['w_gdn_in']", "in_proj", False),
    ("params['layers'][0]['w_gdn_ba']", "in_proj", False),
    ("params['layers'][4]['a_log']", "prep", False),
    ("params['layers'][5]['conv_w']", "conv", False),
    ("params['layers'][6]['w_gdn_out']", "out_proj", False),
    ("params['layers'][3]['w_q']", "attn", False),
    ("params['layers'][7]['w_proj']", "attn", False),
    ("params['layers'][3]['w_gate']", "other", False),
    ("params['embed']", "other", False),
])
def test_scope_rules(op_name, scope, extra):
    assert gdn_scope_reduce.scope_of(op_name, MIXERS) == (scope, extra)
    assert scope in gdn_scope_reduce.SCOPES


def test_the_cell_matches_its_plain_reference():
    run = rehearse(CELL, seconds=0.5)
    r = run.results
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 2
    assert set(r["metrics"]) == {"train_tokens_per_s"}
    assert run.values["compiles_in_window"] == 0
    assert run.facts["scan"]["layers"] == 6
    assert run.facts["flops_per_step"] > 0
    assert set(run.facts["scan"]["chunked_flops"]) == {"fwd", "bwd"}
    # both kinds were traced, the scan over whole chunks, and the program
    # chose its lowerings itself
    begin = run.counters["begin"]
    assert begin["mixer_kinds"]["gdn"] >= 1 <= begin["mixer_kinds"]["attention"]
    assert begin["delta_lowerings"]["chunked"] >= 1
    assert not begin["delta_lowerings"]["padded"]
    assert run.values["delta_chunked_share"] == 100.0
    assert run.values["blocked_head_share"] == 100.0
    # nothing dropped: the held experts computed every slot routed to them
    for at in ("begin", "end"):
        held = run.facts["held"][at]
        assert held["computed"] == held["held"] and not any(held["fallbacks"])
    # no trace on the CPU: the scope readers report nothing and do not raise
    for name, value in run.values.items():
        if "device_ms" in name or "roofline" in name:
            assert value is None, name


def test_a_program_without_the_counter_or_the_scopes_reports_nothing():
    """What the parent of the PR that added them shows the new readers."""
    import types
    from yardstick.layer_metrics import delta_chunked_share
    for counters in ({}, {"begin": {}},
                     {"begin": {"scan_lowerings": {"chunked": 9}}},
                     {"begin": {"delta_lowerings": {"chunked": 0,
                                                    "padded": 0}}}):
        assert delta_chunked_share.read(
            types.SimpleNamespace(counters=counters)) is None
    assert delta_chunked_share.read(types.SimpleNamespace(counters={
        "begin": {"delta_lowerings": {"chunked": 3, "padded": 1}}})) == 75.0
    untraced = types.SimpleNamespace(prepared={}, traced_ops=lambda: 0)
    assert gdn_scope_reduce.per_step_ms(untraced) is None


@pytest.fixture
def fresh_traces():
    """A planted departure must reach the trace: the layer kinds' jitted
    functions are dropped before and after."""
    from tpu_mpi.models import transformer
    transformer._block_traced_once.cache_clear()
    yield transformer
    transformer._block_traced_once.cache_clear()


def test_a_missing_l2_norm_is_not_correct(monkeypatch, fresh_traces):
    """The program with a delta-rule layer's q and k left as the convolution
    gives them."""
    import jax.numpy as jnp
    monkeypatch.setattr(fresh_traces, "_l2_normed",
                        lambda x, eps=1e-6: x.astype(jnp.float32))
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
