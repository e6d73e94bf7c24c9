"""The `lm_kda_train_step` kind (a delta rule decayed a key channel three
layers in four, positionless latent attention, a dense layer first and held
sigmoid experts beside a shared one): the configuration file against the
catalog's keys and against the block the program is handed, the parameter,
FLOP and byte counts against hand counts, the scope rules on op names, the
readers against hand-made runs, and the cell rehearsed at tiny size against
its plain reference, with departures planted and caught. Correctness only;
nothing is measured. Every entry is asserted by name and as a subset, never
by position nor as an exact list, so a later PR's append falsifies nothing
here."""

import json
import os
import types

import pytest

from yardstick import harness, kda_scope_reduce, lm_kda_flops
from test_generators import rehearse

CELL = "kimi-linear-48b-a3b-1c.kda-step-b1s8192"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
# the published widths, by hand from the model's config.json
WIDTHS = {"hidden_size": 2304, "num_attention_heads": 32,
          "num_key_value_heads": 32, "q_lora_rank": None, "kv_lora_rank": 512,
          "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
          "mla_use_nope": True, "intermediate_size": 9216,
          "moe_intermediate_size": 1024, "num_experts_per_token": 8,
          "num_shared_experts": 1, "first_k_dense_replace": 1,
          "moe_router_activation_func": "sigmoid", "moe_renormalize": True,
          "routed_scaling_factor": 2.446, "num_expert_group": 1,
          "topk_group": 1, "moe_layer_freq": 1, "rms_norm_eps": 1e-05,
          "hidden_act": "silu", "tie_word_embeddings": False,
          "num_nextn_predict_layers": 0, "router_num_experts": 256}
# `per_layer` holds at most 128 entries and the benchmark had 128: the six
# readers this kind brings have no entry yet (PERF.md section 7), and the
# cell reads the accepted readers under the accepted entries of the same
# `moves` (the latent layers' among them: the generator states their facts
# under the accepted name), its name appended to their `workloads`
QWEN = "qwen3-next-80b-a3b-1c.gdn-step-b1s8192"
# the six readers this kind brought: (unit, better, source, cells beside CELL)
READERS = {"kda_mixer_device_ms": ("ms", "lower", "device_trace", set()),
           "kda_scan_device_ms": ("ms", "lower", "device_trace", set()),
           "kda_decay_device_ms": ("ms", "lower", "device_trace", set()),
           "kda_scan_roofline": ("%", "higher", "device_trace", set()),
           "delta_kernel_share": ("%", "higher", "program_counter", {QWEN}),
           "channel_decay_share": ("%", "higher", "program_counter", set())}
JOINED = ["step_device_ms", "train_mfu", "device_idle_share.train",
          "fused_attn_share", "grouped_matmul_share", "row_sum_product_share",
          "ssm_conv_device_ms", "delta_chunked_share", "held_moe_device_ms",
          "held_dispatch_device_ms", "shared_expert_device_ms",
          "held_experts_roofline", "held_slot_share", "expert_rows_fill",
          "dense_ffn_device_ms", "kinds_head_loss_device_ms",
          "embed_device_ms", "step_build_s", "kernel_traces", "build_trace_s",
          "build_lower_s", "build_compile_s", "build_cache_misses",
          "blocked_head_share", "latent_attn_device_ms",
          "latent_proj_device_ms", "latent_kernel_roofline"]
TRAIN_CELLS = [
    "flagship-d1024-1c.step-b8s1024", "olmoe-1b-7b-1c.lm-step-b2s4096",
    "k-exaone-236b-a23b-1c.lm-step-b1s8192",
    "openpangu-ultra-moe-718b-1c.lm-step-b1s4096",
    "granite-4.0-h-micro-1c.ssm-step-b1s8192",
    "phi-4-mini-flash-reasoning-1c.sambay-step-b1s8192",
    "qwen3-next-80b-a3b-1c.gdn-step-b1s8192"]


@pytest.fixture(scope="module")
def manifest():
    return harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))


def agrees(conf: dict) -> None:
    """The block handed to the program says what the published keys say, and
    lays the layers out by the model's own lists."""
    m, n = conf["model"], conf["num_hidden_layers"]
    lin = conf["linear_attn_config"]
    assert m["n_layers"] == n == len(m["mixer_kinds"]) \
        == len(m["remat_layers"]) == len(m["ffn_kinds"])
    kinds = harness.load_module(
        os.path.join(harness.HERE, "reference", conf["kind"] + ".py"),
        "ys_reference_" + conf["kind"]).kinds(conf)
    assert m["mixer_kinds"] == [
        {"kda": "kda", "mla": "attention"}[k] for k, _s in kinds]
    assert m["ffn_kinds"] == ["sparse" if s else "dense" for _k, s in kinds]
    assert m["ffn_kinds"].count("dense") == conf["first_k_dense_replace"] == 1
    assert m["d_model"] == conf["hidden_size"]
    assert (m["gdn_key_heads"], m["gdn_value_heads"]) == (lin["num_heads"],) * 2
    assert (m["gdn_key_dim"], m["gdn_value_dim"], m["kda_rank"]) \
        == (lin["head_dim"],) * 3           # the rank: `assumed`
    assert m["gdn_conv"] == lin["short_conv_kernel_size"]
    assert m["n_heads"] == conf["num_attention_heads"] \
        == conf["num_key_value_heads"] and not m["n_kv_heads"]
    assert (m["d_head"], m["d_rope"], m["d_value"], m["kv_latent"]) == (
        conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
        conf["v_head_dim"], conf["kv_lora_rank"])
    assert m["q_latent"] == 0 and conf["q_lora_rank"] is None
    assert m["rope_full_layers"] is False and conf["mla_use_nope"] is True
    assert m["d_ff"] == conf["moe_intermediate_size"]
    assert m["d_ff_dense"] == conf["intermediate_size"] and m["dense_gated"]
    assert m["n_shared_experts"] == conf["num_shared_experts"]
    assert m["n_experts"] == conf["router_num_experts"]
    assert m["experts_held"] == [conf["held_experts_first"],
                                 conf["num_experts"]]
    assert m["experts_per_tok"] == conf["num_experts_per_token"]
    assert m["router_score"] == conf["moe_router_activation_func"] == "sigmoid"
    assert m["router_renorm"] is conf["moe_renormalize"] is True
    assert m["router_scale"] == conf["routed_scaling_factor"]
    assert m["norm_eps"] == conf["rms_norm_eps"]
    assert m["vocab"] == conf["vocab_size"]
    assert m["tie_embeddings"] is conf["tie_word_embeddings"] is False
    assert not {"attn_windows", "norm_unit_offset", "attn_out_gate",
                "shared_expert_gate", "qk_norm_heads", "norm_out"} & set(m)
    assert conf["model_type"] == "kimi_linear" and conf["hidden_act"] == "silu"


def test_the_configuration_keeps_every_published_width(manifest):
    cell = harness.Cell(manifest, CELL)
    conf = cell.config
    assert conf["kind"] == cell.traffic["kind"] == "lm_kda_train_step"
    for key, want in WIDTHS.items():
        assert conf[key] == want, key
    lin = conf["linear_attn_config"]
    assert (lin["num_heads"], lin["head_dim"],
            lin["short_conv_kernel_size"]) == (32, 128, 4)
    assert lin["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
    assert len(lin["kda_layers"]) == 20
    assert conf["reduced"] == REDUCED
    assert (conf["num_hidden_layers"], conf["num_experts"],
            conf["vocab_size"]) == (8, 32, 20480)
    assert 8 * 20480 == 163840 and 8 * 32 == 256 and 20480 % 128 == 0
    for word in ("27", "256", "163840"):
        assert word in conf["reduced_why"], word
    assert "8 chips share each layer" in conf["deployment"]
    assert "256 rows" in conf["deployment"]
    kinds = cell.reference().kinds(conf)
    assert [k for k, _s in kinds] == ["kda", "kda", "kda", "mla"] * 2
    assert [s for _k, s in kinds] == [False] + [True] * 7
    assert conf["model"]["dtype"] == "bfloat16"
    assert conf["model"]["gdn_chunk"] == 64
    assert conf["model"]["remat_layers"] == ["ffn"] * 8
    assert (cell.traffic["batch"], cell.traffic["seq"], cell.traffic["pool"],
            cell.traffic["block_steps"]) == (1, 8192, 16, 2)
    assert set(conf["assumed"]) >= {
        "low_rank_maps", "kda_init", "norm_scales", "l2_norm",
        "selection_bias", "column_order", "auxiliary_loss",
        "sequence_length", "optimizer", "weights", "tokens"}
    assert conf["compare_steps"] == 3 and conf["mesh"] == {
        "dp": 1, "tp": 1, "sp": 1}
    for key in ("loss_tolerance", "logits_tolerance", "update_tolerance",
                "model"):
        assert len(conf[key + "_why"]) > 200, key   # a reason and readings
    assert len(conf["guarantees"]) > 200
    entry = {c["name"]: c for c in manifest["configs"]}[conf["name"]]
    assert entry["source"] == conf["source"]
    assert entry["reduced"] == conf["reduced"]
    assert entry["file"] == "yardstick/configs/kimi-linear-48b-a3b-1c.json"
    agrees(conf)
    agrees(harness.Cell(manifest, CELL, rehearse=True).config)
    assert cell.entry["chips"] == 1 and len(cell.entry["why"]) <= 200


def test_the_configuration_against_the_catalog(manifest):
    """Every key of the catalog row's `config` is in the file under the same
    key with the same value (the nested group whole), but for the depth, the
    experts held and the vocabulary rows held."""
    if not os.path.isfile(CATALOG):
        pytest.skip("the model catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    conf = harness.Cell(manifest, CELL).config
    assert conf["source"] == row["source_url"]
    assert set(row["config"]) <= set(conf)
    changed = {k for k, v in row["config"].items() if conf[k] != v}
    assert changed == set(REDUCED)
    assert (row["config"]["num_hidden_layers"], row["config"]["num_experts"],
            row["config"]["vocab_size"]) == (27, 256, 163840)


def test_the_cell_reports_what_the_benchmark_has_room_for(manifest):
    cell = harness.Cell(manifest, CELL)
    by_name = {m["name"]: m for m in cell.per_layer}
    assert set(JOINED) | set(READERS) | {"compiles_in_window",
                                         "backend_start_s"} <= set(by_name)
    assert len(manifest["per_layer"]) <= 128
    for name in JOINED:         # joined the cells the entry had
        assert {CELL} < set(by_name[name]["workloads"]), name
        assert by_name[name]["moves"] == ("setup_s" if name.startswith(
            ("step_build_s", "kernel_traces", "build_"))
            else "train_tokens_per_s"), name
    for _spec, mod in cell.readers():
        assert hasattr(mod, "read")
    # the six readers this kind brought have their entries since PR 50 (PR 48
    # found `per_layer` full at 128): each by name, unit, `moves` and cells
    for reader, (unit, better, source, others) in READERS.items():
        spec = dict(by_name[reader])
        assert {CELL} | others <= set(spec.pop("workloads")), reader
        assert spec == {"name": reader, "unit": unit, "better": better,
                        "source": source, "layer": "train step",
                        "moves": "train_tokens_per_s"}, reader
        mod = harness.load_module(os.path.join(
            harness.HERE, "layer_metrics", reader + ".py"), "ys_l_" + reader)
        assert hasattr(mod, "read") and mod.__doc__.startswith(reader)
        assert (reader in cells_of(manifest, QWEN)) == (QWEN in others)
    # the cell reports the rate, with the accepted train cells
    (spec,) = [m for m in manifest["end_to_end"]
               if m["name"] == "train_tokens_per_s"]
    assert set(TRAIN_CELLS) | {CELL} <= set(spec["workloads"])
    assert {m["name"] for m in cell.end_to_end} == {"train_tokens_per_s",
                                                    "setup_s"}
    for w in manifest["workloads"]:
        names = cells_of(manifest, w["name"])
        assert len(set(names)) == len(names)
    # 11 cells, still one of four chips
    assert len(manifest["workloads"]) >= 11
    assert [w["chips"] for w in manifest["workloads"]].count(4) == 1


def test_the_delta_rule_cells_five_entries_stand(manifest):
    """PR 45's five entries by name, the Qwen3-Next cell among the cells of
    each and this cell in the one whose reader finds something here; what
    else that cell reports is `test_lm_gdn_train_step.py`'s to hold."""
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name, (unit, source) in {
            "gdn_mixer_device_ms": ("ms", "device_trace"),
            "gdn_scan_device_ms": ("ms", "device_trace"),
            "gdn_scan_roofline": ("%", "device_trace"),
            "gated_attn_device_ms": ("ms", "device_trace"),
            "delta_chunked_share": ("%", "program_counter")}.items():
        spec = by_name[name]
        assert QWEN in spec["workloads"], name
        assert (CELL in spec["workloads"]) == (
            name == "delta_chunked_share"), name
        assert (spec["unit"], spec["source"], spec["layer"], spec["moves"]) \
            == (unit, source, "train step", "train_tokens_per_s"), name


def cells_of(manifest, cell: str) -> list:
    """The names of the per-layer metrics a cell reports."""
    return [m["name"] for m in harness.Cell(manifest, cell).per_layer]


def test_parameters_flops_and_bytes_against_a_hand_count(manifest):
    """ISSUE 48's count, by hand."""
    model = harness.Cell(manifest, CELL).config["model"]
    t, d, f = 8192, 2304, 1024
    kda = 3 * d * 4096 + d * (2 * 128 + 32) + 2 * 128 * 4096 + 4096 * d
    kda_rest = 3 * 4096 * 4 + 4096 + 32 + 128
    mla = d * 6144 + d * 576 + 512 * 8192 + 4096 * d
    mla_rest = 512
    experts = d * 256 + 3 * d * f + 32 * 3 * d * f
    dense = 3 * d * 9216
    assert kda + kda_rest == 39_514_272             # ISSUE 48
    assert mla + mla_rest == 29_114_880
    assert experts == 234_160_128 and dense == 63_700_992
    assert lm_kda_flops.mixer_matrix_params(model, "kda") == kda
    assert lm_kda_flops.mixer_other_params(model, "kda") == kda_rest
    assert lm_kda_flops.mixer_matrix_params(model, "latent") == mla
    assert lm_kda_flops.mixer_other_params(model, "latent") == mla_rest
    assert lm_kda_flops.ffn_matrix_params(model, True) == experts
    assert lm_kda_flops.ffn_matrix_params(model, False) == dense
    first = kda + kda_rest + dense + 2 * d
    kda_layer = kda + kda_rest + experts + 2 * d
    mla_layer = mla + mla_rest + experts + 2 * d
    assert (first, kda_layer, mla_layer) == (
        103_219_872, 273_679_008, 263_279_616)
    assert lm_kda_flops.params_count(model) \
        == first + 5 * kda_layer + 2 * mla_layer + 2 * 20480 * d + d \
        == 2_092_548_288
    assert lm_kda_flops.layer_mixers(model) == ["kda", "kda", "kda",
                                                "latent"] * 2
    assert lm_kda_flops.sparse_layers(model) == 7
    # forward: every matrix once a token; the scan as the recurrence (three
    # products with a [128 x 128] state a head and token); 32 heads' scores
    # over the causal pairs, 192 + 128 wide; 256 rows an expert
    rows = t * 8 * 32 // 256
    assert rows == 32 * 256
    parts = lm_kda_flops.flops_by_part(model, 1, t)
    assert parts == {
        "kda_matrices": 6 * 2 * t * kda,
        "kda_scan": 6 * 3 * 2 * t * 32 * 128 * 128,
        "latent_matrices": 2 * 2 * t * mla,
        "latent_scores": 2 * 2 * 32 * (t * (t + 1) / 2) * 320,
        "dense_ffn": 2 * t * dense,
        "router": 7 * 2 * t * d * 256,
        "shared": 7 * 2 * t * 3 * d * f,
        "held_experts": 7 * 2 * rows * 3 * d * f,
        "head": 2 * t * d * 20480}
    fwd = sum(parts.values())
    assert lm_kda_flops.flops_per_step(model, 1, t) == 3 * fwd
    assert lm_kda_flops.flops_per_step(model, 1, t, held_rows=rows) == 3 * fwd
    assert 29.5e12 < 3 * fwd < 30.5e12              # ISSUE 48: 30 T a step
    assert 0.07 < parts["head"] / fwd < 0.08        # ISSUE 48: 7.6%
    assert 78e6 < 2 * kda < 80e6 and 58e6 < 2 * mla < 59e6      # a token
    # the scan alone, one layer: the chunked form's matrix FLOPs at chunk 64
    chunks, length = t // 64, 64
    lower = length * (length - 1) // 2
    scan = chunks * 32 * (2 * 2 * length * length * 128 + 2 * lower * 256
                          + 3 * 2 * length * 128 * 128
                          + 2 * length * length * 128)
    assert lm_kda_flops.scan_chunked_flops(model, 1, t) == {
        "fwd": scan, "bwd": 2 * scan}
    assert scan == 42_882_564_096                   # 0.65 ms with backward
    # its least bytes: q, k, v in bfloat16, g float32 A CHANNEL, beta, o out
    inputs = 2 * 3 * 4096 + 4 * 4096 + 4 * 32
    least = lm_kda_flops.scan_least_bytes(model, 1, t, 2)
    assert least == {"fwd": t * (inputs + 2 * 4096),
                     "bwd": t * (2 * inputs + 2 * 4096)}
    assert least == {"fwd": 403_701_760, "bwd": 740_294_656}   # 1.40 ms
    assert 4 * 4096 / (inputs + 2 * 4096) > 0.33    # the decay's share


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
        == lm_kda_flops.params_count(model) == 2_092_548_288


MIXERS = ["kda", "kda", "kda", "latent"] * 2


@pytest.mark.parametrize("op_name, scope", [
    ("jit(local_step)/jvp(layer_2)/jit(block)/mixer/checkpoint/in_proj/"
     "dot_general", "in_proj"),
    ("jit(local_step)/jvp(layer_0)/jit(block)/mixer/checkpoint/conv/"
     "conv_silu_fwd/pallas_call", "conv"),
    ("jit(local_step)/transpose(jvp(layer_0))/jit(block)/mixer/mixer/"
     "checkpoint/rematted_computation/prep/rsqrt", "prep"),
    ("jit(local_step)/transpose(jvp(layer_6))/jit(block)/mixer/mixer/"
     "checkpoint/rematted_computation/decay/dot_general", "decay"),
    ("jit(local_step)/jvp(layer_5)/jit(block)/mixer/checkpoint/decay/"
     "softplus", "decay"),
    ("jit(local_step)/transpose(jvp(layer_4))/jit(block)/mixer/mixer/"
     "checkpoint/scan/checkpoint/rematted_computation/while/body/dot_general",
     "scan"),
    ("jit(local_step)/jvp(layer_6)/jit(block)/mixer/checkpoint/scan/"
     "checkpoint/while", "scan"),
    ("jit(local_step)/jvp(layer_5)/jit(block)/mixer/checkpoint/gate_norm/"
     "checkpoint/logistic", "gate_norm"),
    ("jit(local_step)/transpose(jvp(layer_1))/jit(block)/mixer/mixer/"
     "checkpoint/out_proj/dot_general", "out_proj"),
    ("jit(local_step)/jvp(layer_1)/jit(block)/mixer/add", "kda_rest"),
    # the latent layers are `latent_scope_reduce`'s; a kda layer has no
    # `attn`, a latent layer no `mixer`
    ("jit(local_step)/jvp(layer_3)/jit(block)/attn/jit(attend)/"
     "causal_attention_fwd/pallas_call", "other"),
    ("jit(local_step)/jvp(layer_3)/jit(block)/attn/q_proj/dot_general",
     "other"),
    ("jit(local_step)/jvp(layer_0)/jit(block)/attn/dot_general", "other"),
    ("jit(local_step)/jvp(layer_3)/jit(block)/mixer/scan/while", "other"),
    ("jit(local_step)/jvp(layer_0)/jit(block)/mlp/dense/dot_general",
     "other"),
    ("jit(local_step)/jvp(layer_4)/jit(block)/mlp/shared/dot_general",
     "other"),
    ("jit(local_step)/jvp(head_loss)/dot_general", "other"),
    ("reduce_sum", "other"),
    ("params['layers'][2]['w_kda_in']", "in_proj"),
    ("params['layers'][0]['w_kda_low']", "in_proj"),
    ("params['layers'][4]['w_kda_f']", "decay"),
    ("params['layers'][4]['dt_bias']", "decay"),
    ("params['layers'][5]['conv_w']", "conv"),
    ("params['layers'][5]['w_kda_g']", "gate_norm"),
    ("params['layers'][6]['w_kda_out']", "out_proj"),
    ("params['layers'][3]['w_q']", "other"),
    ("params['layers'][7]['w_ukv']", "other"),
    ("params['layers'][3]['w_gate']", "other"),
    ("params['embed']", "other"),
])
def test_scope_rules(op_name, scope):
    assert kda_scope_reduce.scope_of(op_name, MIXERS) == scope
    assert scope in kda_scope_reduce.SCOPES


def test_the_cell_matches_its_plain_reference(fresh_traces):
    from tpu_mpi import perfvars
    # the counters are the process's: another file's rehearsal (the
    # Qwen3-Next cell's, a decay a head) has counted its kinds in them
    perfvars.reset()
    run = rehearse(CELL, seconds=0.5)
    r = run.results
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 2
    assert set(r["metrics"]) == {"train_tokens_per_s"}
    assert run.values["compiles_in_window"] == 0
    assert run.facts["kda_scan"]["layers"] == 6
    assert run.facts["latent"]["layers"] == 2
    assert "scan" not in run.facts and run.facts["flops_per_step"] > 0
    assert set(run.facts["kda_scan"]["chunked_flops"]) == {"fwd", "bwd"}
    model = harness.Cell(harness.load_json(os.path.join(
        harness.ROOT, "BENCHMARK.json")), CELL, rehearse=True).config["model"]
    computed = [n for when in run.facts["held"].values()
                for n in when["computed"]]
    assert len(computed) == 2 * 7       # the dense layer routes nothing
    assert run.facts["flops_per_step"] == lm_kda_flops.flops_per_step(
        model, 2, 32, held_rows=sum(computed) / len(computed))
    # both kinds were traced, the scan over whole chunks with a decay a
    # channel on the plain path, and the program chose its lowerings itself
    begin = run.counters["begin"]
    assert begin["mixer_kinds"]["kda"] >= 1 <= begin["mixer_kinds"]["attention"]
    assert begin["delta_lowerings"]["chunked"] >= 1
    assert not begin["delta_lowerings"]["padded"]
    assert begin["delta_decays"]["channel"] >= 1
    assert not begin["delta_decays"]["head"]
    assert begin["attn_kinds"] == {"latent": "plain"}
    assert begin["rope_forms"] == {"dense": 0, "halves": 0}
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
    # the readers without an entry, on this run's counters
    from yardstick.layer_metrics import channel_decay_share, delta_kernel_share
    assert channel_decay_share.read(run) == 100.0
    assert delta_kernel_share.read(run) == 0.0


def test_every_router_sends_each_share_one_row_a_token(manifest):
    """The cell's routers are tied over the 8 shares of 32 experts, so a
    token's best expert comes with its twins on the other chips and this
    chip gets one row a token in every layer, whatever the seed; the
    Qwen3-Next cell, whose traffic names no shares, keeps its routers as
    drawn."""
    import jax.numpy as jnp
    from yardstick.generators.lm_gdn_train_step import tied_routers
    cell = harness.Cell(manifest, CELL)
    held, n = cell.config["model"]["experts_held"][1], \
        cell.config["model"]["n_experts"]
    assert cell.traffic["router_shares"] * held == n == 256
    assert "router_shares" not in harness.Cell(manifest, QWEN).traffic
    w = jnp.arange(3 * 12, dtype=jnp.float32).reshape(3, 12)
    params = {"embed": w, "layers": [{"w_router": w, "w_in": w},
                                     {"w_in": w}]}
    tied = tied_routers(params, 4, 3)
    assert (tied["layers"][0]["w_router"] ==
            jnp.concatenate([w[:, :4]] * 3, axis=1)).all()
    assert tied["embed"] is w and tied["layers"][1]["w_in"] is w
    assert tied["layers"][0]["w_in"] is w
    with pytest.raises(ValueError):
        tied_routers(params, 4, 2)
    # at the rehearsal's size: every layer's held experts get the balanced
    # 64 rows before the window (five steps have trained the routers: a
    # token or two have left their twins), at two seeds
    for seed in (3, 2 ** 31 + 11):
        run = rehearse(CELL, seconds=0.2, seed=seed)
        begin = run.facts["held"]["begin"]["held"]
        assert len(begin) == 7 and all(abs(n - 64) <= 3 for n in begin), \
            (seed, begin)
        assert run.results["correct"]


def test_a_program_without_the_counter_or_the_scopes_reports_nothing():
    """What the parent of the PR that added them shows the new readers."""
    from yardstick.layer_metrics import (channel_decay_share,
                                         delta_kernel_share, kda_scan_roofline)
    for counters in ({}, {"begin": {}},
                     {"begin": {"delta_lowerings": {"chunked": 9}}},
                     {"begin": {"delta_decays": {"head": 0, "channel": 0},
                                "delta_kernel_lowerings": {"kernel": 0,
                                                           "plain": 0}}}):
        run = types.SimpleNamespace(counters=counters)
        assert channel_decay_share.read(run) is None
        assert delta_kernel_share.read(run) is None
    run = types.SimpleNamespace(counters={"begin": {
        "delta_decays": {"head": 1, "channel": 3},
        "delta_kernel_lowerings": {"kernel": 1, "plain": 3}}})
    assert channel_decay_share.read(run) == 75.0
    assert delta_kernel_share.read(run) == 25.0
    untraced = types.SimpleNamespace(prepared={}, traced_ops=lambda: 0,
                                     facts={}, peaks=None)
    assert kda_scope_reduce.per_step(untraced) is None
    assert kda_scan_roofline.read(untraced) is None


def test_the_rooflines_from_a_hand_made_reduction():
    """The two shares from scope times and facts given by hand: the larger
    bound over the time, and the kernel's products over its calls."""
    from yardstick import latent_scope_reduce
    from yardstick.layer_metrics import (kda_mixer_device_ms,
                                         kda_scan_roofline,
                                         latent_attn_device_ms,
                                         latent_kernel_roofline,
                                         latent_proj_device_ms)
    ms = dict.fromkeys(kda_scope_reduce.SCOPES, 1.0)
    ms.update(scan=200.0)
    rows = []
    attn = dict.fromkeys(latent_scope_reduce.SCOPES, 0.0)   # nothing under
    #                               q_latent, rope or a sandwich's norm here
    attn.update(kernel_fwd=10.0, kernel_bwd=30.0, kv_latent=5.0, out=3.0,
                attn_rest=4.0)
    calls = {"fwd": 2.0, "bwd": 2.0}
    run = types.SimpleNamespace(
        prepared={kda_scope_reduce.KEY: {"ms": ms},
                  latent_scope_reduce.KEY: {"ms": attn, "calls": calls}},
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        facts={"kda_scan": {"layers": 6,
                            "chunked_flops": {"fwd": 42.9e9, "bwd": 85.8e9},
                            "least_bytes": {"fwd": 403.7e6, "bwd": 740.3e6}},
               "latent": {"kernel_flops": {"fwd": 0.394e12,
                                           "bwd": 0.985e12}}},
        row=rows.append)
    by_bytes = (403.7e6 + 740.3e6) / 819e9
    assert kda_scan_roofline.read(run) == pytest.approx(
        100 * 6 * by_bytes * 1e3 / 200.0)
    assert "bound by its bytes" in rows[0]
    assert kda_mixer_device_ms.read(run) == 200.0 + 7.0
    # the latent layers through the accepted readers, the facts under the
    # accepted name: the one query product lies under `q_proj`, no scope of
    # theirs, so it counts with the half and not with the latent's products
    assert latent_kernel_roofline.read(run) == pytest.approx(
        100 * 2 * (0.394e12 + 0.985e12) / 197e12 * 1e3 / 40.0)
    assert latent_attn_device_ms.read(run) == 52.0
    assert latent_proj_device_ms.read(run) == 5.0


@pytest.fixture
def fresh_traces():
    """A planted departure must reach the trace: the layer kinds' jitted
    functions are dropped before and after."""
    from tpu_mpi.models import transformer
    transformer._block_traced_once.cache_clear()
    yield transformer
    transformer._block_traced_once.cache_clear()


def test_a_missing_l2_norm_is_not_correct(monkeypatch, fresh_traces):
    """The program with a KDA layer's q and k left as the convolution gives
    them."""
    import jax.numpy as jnp
    monkeypatch.setattr(fresh_traces, "_l2_normed",
                        lambda x, eps=1e-6: x.astype(jnp.float32))
    assert not rehearse(CELL, seconds=0.2).results["correct"]


def test_a_decay_averaged_over_its_channels_is_not_correct(monkeypatch,
                                                           fresh_traces):
    """The program with the vector decay replaced by its head's mean: the
    scalar rule under this model's name."""
    import jax.numpy as jnp
    from tpu_mpi.parallel import delta
    honest = delta.delta_scan
    monkeypatch.setattr(delta, "delta_scan", lambda q, k, v, g, beta, chunk:
                        honest(q, k, v, jnp.mean(g, axis=-1), beta, chunk))
    assert not rehearse(CELL, seconds=0.2).results["correct"]


def scan_readings():
    """`scan_off_by` of the rehearse cell, without the loop."""
    import jax
    manifest = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    cell = harness.Cell(manifest, CELL, rehearse=True)
    run = harness.Run(cell, 3, 0.2, False, True, 0.0)
    run.devices = list(jax.devices()[:1])
    return cell.generator().scan_off_by(run), float(
        cell.config["scan_tolerance"])


@pytest.mark.parametrize("slip", ["none", "state", "sums", "one decay a head"])
def test_the_scan_alone_against_the_recurrence(monkeypatch, slip):
    """The program's `delta_scan` on the first KDA layer's operands agrees
    with the recurrence a token at a time within `scan_tolerance`; the
    state after each chunk or a chunk's decay sums rounded to bfloat16 (the
    probe the loop's checks do not see on the chip) miss it, and so does
    the scalar rule."""
    import jax
    import jax.numpy as jnp
    from tpu_mpi.parallel import delta
    bf16 = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)     # noqa: E731
    jax.clear_caches()      # `_chunked` is a `jax.checkpoint`, traced once a
    #                         shape: a planted slip must reach its trace
    if slip == "state":
        honest = delta._chain_step
        monkeypatch.setattr(delta, "_chain_step", lambda s, at, dtype: (
            lambda after, u: (bf16(after), u))(*honest(s, at, dtype)))
    elif slip == "sums":
        honest = jnp.cumsum
        monkeypatch.setattr(delta.jnp, "cumsum",
                            lambda a, axis=None: bf16(honest(a, axis=axis)))
    elif slip == "one decay a head":
        honest = delta.delta_scan
        monkeypatch.setattr(delta, "delta_scan", lambda q, k, v, g, beta, c:
                            honest(q, k, v, jnp.mean(g, axis=-1), beta, c))
    scan, limit = scan_readings()
    jax.clear_caches()
    assert len(scan["by_head"]) == 4 and scan["rounding"] == 0.0   # float32
    assert min(scan["by_head"]) <= scan["all"] <= max(scan["by_head"])
    assert (scan["all"] <= limit) == (slip == "none"), scan


def test_a_scan_off_its_limit_is_not_correct(monkeypatch):
    """The reading decides `correct` with the loop's own."""
    gen = harness.Cell(harness.load_json(os.path.join(
        harness.ROOT, "BENCHMARK.json")), CELL, rehearse=True).generator()
    monkeypatch.setattr(gen, "scan_off_by", lambda run: {
        "all": 2e-5, "by_head": [2e-5] * 4, "rounding": 0.0})
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
