"""The `lm_ssm_train_step` kind (state-space layers nine to one beside
position-free grouped-query attention, no experts): the configuration file
against the catalog's keys and against the block the program is handed, the
FLOP, parameter and byte counts against hand counts, the scope rules on op
names, and the cell rehearsed at tiny size against its plain reference.
Correctness only; nothing is measured. Every entry is asserted by name and by
no position, so a later PR's append falsifies nothing here."""

import json
import os

import pytest

from yardstick import harness, lm_ssm_flops, ssm_scope_reduce
from test_generators import rehearse

CELL = "granite-4.0-h-micro-1c.ssm-step-b1s8192"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the published widths, by hand from the model's config.json
WIDTHS = {"hidden_size": 2048, "intermediate_size": 8192,
          "shared_intermediate_size": 8192, "mamba_n_heads": 64,
          "mamba_d_head": 64, "mamba_d_state": 128, "mamba_d_conv": 4,
          "mamba_chunk_size": 256, "mamba_expand": 2, "mamba_n_groups": 1,
          "num_attention_heads": 32, "num_key_value_heads": 8,
          "vocab_size": 100352, "attention_multiplier": 0.015625,
          "embedding_multiplier": 12, "residual_multiplier": 0.22,
          "logits_scaling": 8, "rms_norm_eps": 1e-05,
          "num_local_experts": 0, "num_experts_per_tok": 0}
NEW = ["ssm_mixer_device_ms", "ssm_scan_device_ms", "ssm_conv_device_ms",
       "ssm_scan_roofline"]
# the accepted readers this cell shares with others, by their entries' names
# (PR 50 folded the tags that named a cell into the readers' cell lists)
SHARED = ["step_device_ms", "train_mfu", "device_idle_share.train",
          "fused_attn_share", "dense_ffn_device_ms",
          "kinds_head_loss_device_ms", "embed_device_ms",
          "row_sum_product_share", "step_build_s", "kernel_traces",
          "build_trace_s", "build_lower_s", "build_compile_s",
          "build_cache_misses"]


@pytest.fixture(scope="module")
def manifest():
    return harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))


def agrees(conf: dict) -> None:
    """The block handed to the program says what the published keys say."""
    m, n = conf["model"], conf["num_hidden_layers"]
    assert m["n_layers"] == n == len(m["mixer_kinds"])
    assert m["mixer_kinds"] == [
        {"mamba": "ssm", "attention": "attention"}[t]
        for t in conf["layer_types"][:n]]
    assert m["d_model"] == conf["hidden_size"]
    assert m["d_ff"] == conf["shared_intermediate_size"] \
        == conf["intermediate_size"]
    assert (m["ssm_heads"], m["ssm_head_dim"], m["ssm_state"]) == (
        conf["mamba_n_heads"], conf["mamba_d_head"], conf["mamba_d_state"])
    assert (m["ssm_conv"], m["ssm_chunk"], m["ssm_expand"]) == (
        conf["mamba_d_conv"], conf["mamba_chunk_size"], conf["mamba_expand"])
    assert m["ssm_heads"] * m["ssm_head_dim"] == m["ssm_expand"] * m["d_model"]
    assert conf["mamba_n_groups"] == 1 and conf["mamba_conv_bias"] \
        and not conf["mamba_proj_bias"]
    assert (m["n_heads"], m["n_kv_heads"]) == (
        conf["num_attention_heads"], conf["num_key_value_heads"])
    assert m["d_head"] == conf["hidden_size"] // conf["num_attention_heads"]
    assert m["attn_scale"] == conf["attention_multiplier"]
    assert m["embed_multiplier"] == conf["embedding_multiplier"]
    assert m["residual_multiplier"] == conf["residual_multiplier"]
    assert m["logits_divisor"] == conf["logits_scaling"]
    assert m["rope_full_layers"] is False \
        and conf["position_embedding_type"] == "nope"
    assert m["dense_gated"] and conf["hidden_act"] == "silu"
    assert conf["num_local_experts"] == 0 and not {
        "n_experts", "ffn_kinds", "experts_held", "attn_windows"} & set(m)
    assert m["vocab"] == conf["vocab_size"]
    assert m["norm_eps"] == conf["rms_norm_eps"]
    assert m["tie_embeddings"] is conf["tie_word_embeddings"] is True
    assert conf["model_type"] == "granitemoehybrid"
    assert not conf["attention_bias"]


def test_the_configuration_keeps_every_published_width(manifest):
    cell = harness.Cell(manifest, CELL)
    conf = cell.config
    assert conf["kind"] == cell.traffic["kind"] == "lm_ssm_train_step"
    for key, want in WIDTHS.items():
        assert conf[key] == want, key
    assert conf["reduced"] == ["num_hidden_layers"]
    assert conf["num_hidden_layers"] == 10 and len(conf["layer_types"]) == 40
    kinds = conf["layer_types"][:10]
    assert kinds.count("mamba") == 9 and kinds[5] == "attention"
    assert conf["layer_types"] == kinds * 4     # one whole period is here
    assert (cell.traffic["batch"], cell.traffic["seq"], cell.traffic["pool"],
            cell.traffic["block_steps"]) == (1, 8192, 16, 2)
    assert set(conf["assumed"]) >= {
        "ssm_init", "time_step_limit", "sequence_length", "optimizer",
        "aux_loss", "weights", "tokens"}
    assert conf["compare_steps"] == 3 and conf["mesh"] == {
        "dp": 1, "tp": 1, "sp": 1}
    for key in ("loss_tolerance", "logits_tolerance", "update_tolerance",
                "model"):
        assert len(conf[key + "_why"]) > 200, key   # a reason and readings
    entry = {c["name"]: c for c in manifest["configs"]}[conf["name"]]
    assert entry["source"] == conf["source"]
    assert entry["reduced"] == conf["reduced"]
    assert entry["file"] == "yardstick/configs/granite-4.0-h-micro-1c.json"
    agrees(conf)
    agrees(harness.Cell(manifest, CELL, rehearse=True).config)
    assert cell.entry["chips"] == 1
    assert CELL in next(m for m in manifest["end_to_end"]
                        if m["name"] == "train_tokens_per_s")["workloads"]


def test_the_configuration_against_the_catalog(manifest):
    """Every key of the catalog row's `config` is in the file under the same
    key with the same value, but for the depth."""
    if not os.path.isfile(CATALOG):
        pytest.skip("the model catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "granite-4.0-h-micro")
    conf = harness.Cell(manifest, CELL).config
    assert conf["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if conf.get(k) != v}
    assert changed == {"num_hidden_layers"}
    assert row["config"]["num_hidden_layers"] == 40


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
    assert by_name["ssm_scan_roofline"]["unit"] == "%"
    assert by_name["ssm_scan_roofline"]["better"] == "higher"
    for _spec, mod in cell.readers():
        assert hasattr(mod, "read")
    # one four-chip cell of eight: a second needs eight cells, which are here
    chips = [w["chips"] for w in manifest["workloads"]]
    assert chips.count(4) == 1 and len(chips) >= 8


def test_parameters_flops_and_bytes_against_a_hand_count(manifest):
    model = harness.Cell(manifest, CELL).config["model"]
    t = 8192
    mixer = 2048 * (4096 + 4352 + 64) + 4096 * 2048      # in_proj, out_proj
    rest = 4352 * 4 + 4352 + 3 * 64 + 4096      # conv, its bias, A D dt, norm
    ffn = 3 * 2048 * 8192
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512
    assert mixer + rest == 25_847_232
    assert lm_ssm_flops.mixer_matrix_params(model, "ssm") == mixer
    assert lm_ssm_flops.mixer_matrix_params(model, "attention") == attn
    want = 9 * (mixer + rest + ffn + 4096) + attn + ffn + 4096 \
        + 100352 * 2048 + 2048
    assert lm_ssm_flops.params_count(model) == want == 951_991_232
    # forward: every matrix once a token, the causal scores, the scan as
    # the recurrence (an update and a read of a [64 x 128] state a head)
    scores = 2 * 2 * 32 * 64 * (t * (t + 1) // 2)
    scan = 2 * 2 * t * 64 * 64 * 128
    fwd = 2 * t * (9 * mixer + attn + 10 * ffn + 100352 * 2048) \
        + scores + 9 * scan
    assert lm_ssm_flops.flops_per_step(model, 1, t) == 3 * fwd
    assert 47.5e12 < 3 * fwd < 48.5e12          # ISSUE 37: about 49 T a step
    assert 0.20 < 3 * 2 * t * 100352 * 2048 / (3 * fwd) < 0.22   # the head
    assert 0.009 < 3 * 9 * scan / (3 * fwd) < 0.011              # the scan
    # the scan's least bytes a layer: x, B, C, dt in and y out, in bfloat16
    inputs = 4096 + 128 + 128 + 64
    least = lm_ssm_flops.scan_least_bytes(model, 1, t, 2)
    assert least == {"fwd": 2 * t * (inputs + 4096),
                     "bwd": 2 * t * (inputs + 4096 + inputs)}
    assert sum(least.values()) == 351_272_960   # 0.43 ms at 819 GB/s
    assert lm_ssm_flops.layer_mixers(model) == \
        ["ssm"] * 5 + ["attention"] + ["ssm"] * 4


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
        == lm_ssm_flops.params_count(model)


@pytest.mark.parametrize("op_name, scope", [
    ("jit(local_step)/jvp(layer_2)/jit(block)/mixer/in_proj/dot_general",
     "in_proj"),
    ("jit(local_step)/transpose(jvp(layer_0))/jit(block)/mixer/conv/mul",
     "conv"),
    ("jit(local_step)/jvp(layer_9)/jit(block)/mixer/scan/exp", "scan"),
    ("jit(local_step)/transpose(jvp(layer_7))/jit(block)/mixer/scan/mixer/"
     "scan/checkpoint/rematted_computation/bchls,bcshp->bclhp/dot_general",
     "scan"),
    ("jit(local_step)/jvp(layer_1)/jit(block)/mixer/gate_norm/mul",
     "gate_norm"),
    ("jit(local_step)/transpose(jvp(layer_1))/jit(block)/mixer/out_proj/"
     "dot_general", "out_proj"),
    ("jit(local_step)/jvp(layer_3)/jit(block)/mixer/add", "mixer_rest"),
    ("jit(local_step)/jvp(layer_5)/jit(block)/attn/jit(attend)/"
     "causal_attention_fwd/pallas_call", "other"),
    ("jit(local_step)/jvp(layer_0)/jit(block)/mlp/dense/dot_general",
     "other"),
    ("jit(local_step)/jvp(head_loss)/dot_general", "other"),
    ("reduce_sum", "other"),
    ("params['layers'][3]['w_ssm_in']", "in_proj"),
    ("params['layers'][0]['conv_w']", "conv"),
    ("params['layers'][8]['a_log']", "scan"),
    ("params['layers'][2]['w_ssm_out']", "out_proj"),
    ("params['layers'][5]['w_proj']", "other"),
    ("params['embed']", "other"),
])
def test_scope_rules(op_name, scope):
    assert ssm_scope_reduce.scope_of(op_name) == scope
    assert scope in ssm_scope_reduce.SCOPES


def test_the_cell_matches_its_plain_reference():
    run = rehearse(CELL, seconds=0.5)
    r = run.results
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 2
    assert set(r["metrics"]) == {"train_tokens_per_s"}
    assert run.values["compiles_in_window"] == 0
    assert run.facts["scan"]["layers"] == 9
    assert run.facts["flops_per_step"] > 0
    # both mixers were traced, the scan in its chunked form, and the program
    # chose its attention's lowering once
    begin = run.counters["begin"]
    assert begin["mixer_kinds"]["ssm"] >= 1 <= begin["mixer_kinds"]["attention"]
    assert begin["scan_lowerings"]["chunked"] >= 1
    assert not begin["scan_lowerings"]["padded"]
    # no trace on the CPU: the scope readers report nothing and do not raise
    for name, value in run.values.items():
        if "device_ms" in name or "roofline" in name:
            assert value is None, name


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
