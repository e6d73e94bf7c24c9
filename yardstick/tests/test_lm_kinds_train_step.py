"""The `lm_kinds_train_step` kind (a stack of layer kinds, one chip's share
of the experts): the configuration file against the catalog's keys and
against the block the program is handed, the FLOP counts against hand
counts, the scope rules on op names, and the cell rehearsed at tiny size
against its plain reference. Correctness only; nothing is measured."""

import json
import os

import pytest

from yardstick import harness, kinds_scope_reduce, lm_kinds_flops
from test_generators import rehearse

CELL = "k-exaone-236b-a23b-1c.lm-step-b1s8192"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers": (48, 5), "num_experts": (128, 8),
           "vocab_size": (153600, 19200), "num_nextn_predict_layers": (1, 0)}
# the published widths, by hand from the model's config.json
WIDTHS = {"hidden_size": 6144, "head_dim": 128, "num_attention_heads": 64,
          "num_key_value_heads": 8, "intermediate_size": 18432,
          "moe_intermediate_size": 2048, "sliding_window": 128,
          "num_experts_per_tok": 8, "num_shared_experts": 1,
          "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-05}


@pytest.fixture(scope="module")
def manifest():
    return harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))


def agrees(conf: dict) -> None:
    """The block handed to the program says what the published keys say."""
    m, n = conf["model"], conf["num_hidden_layers"]
    assert m["d_model"] == conf["hidden_size"]
    assert (m["n_heads"], m["n_kv_heads"], m["d_head"]) == (
        conf["num_attention_heads"], conf["num_key_value_heads"],
        conf["head_dim"])
    assert m["n_layers"] == n == len(m["attn_windows"]) == len(m["ffn_kinds"])
    assert m["attn_windows"] == [
        conf["sliding_window"] if kind == "sliding_attention" else 0
        for kind in conf["layer_types"][:n]]
    assert m["ffn_kinds"] == conf["mlp_layer_types"][:n]
    assert m["ffn_kinds"][:conf["first_k_dense_replace"]] == \
        ["dense"] * conf["first_k_dense_replace"]
    assert (m["d_ff"], m["d_ff_dense"]) == (conf["moe_intermediate_size"],
                                            conf["intermediate_size"])
    assert m["n_experts"] == conf["router_num_experts"]
    assert m["experts_held"] == [conf["held_experts_first"],
                                 conf["num_experts"]]
    assert m["experts_per_tok"] == conf["num_experts_per_tok"]
    assert m["n_shared_experts"] == conf["num_shared_experts"]
    assert m["router_score"] == conf["scoring_func"] == "sigmoid"
    assert m["router_renorm"] == conf["norm_topk_prob"]
    assert m["router_scale"] == conf["routed_scaling_factor"]
    assert conf["n_group"] == conf["topk_group"] == 1
    assert m["rope_theta"] == conf["rope_parameters"]["rope_theta"]
    assert not m["rope_full_layers"] and m["qk_norm_heads"]
    assert m["dense_gated"] and conf["hidden_act"] == "silu"
    assert m["vocab"] == conf["vocab_size"]
    assert m["norm_eps"] == conf["rms_norm_eps"]
    assert m["tie_embeddings"] == conf["tie_word_embeddings"]
    assert conf["model_type"] == "exaone_moe"


def test_the_configuration_keeps_every_published_width(manifest):
    cell = harness.Cell(manifest, CELL)
    conf = cell.config
    assert conf["kind"] == cell.traffic["kind"] == "lm_kinds_train_step"
    for key, want in WIDTHS.items():
        assert conf[key] == want, key
    assert set(conf["reduced"]) == set(REDUCED)
    for key, (_published, here) in REDUCED.items():
        assert conf[key] == here
    assert conf["router_num_experts"] == 128
    assert (cell.traffic["batch"], cell.traffic["seq"], cell.traffic["pool"],
            cell.traffic["block_steps"]) == (1, 8192, 16, 2)
    assert set(conf["assumed"]) >= {"norm_placement", "qk_norm", "rope",
                                    "router_bias", "aux_loss", "optimizer",
                                    "weights", "tokens"}
    assert conf["compare_steps"] == 3 and "dropped" in conf["guarantees"]
    entry = {c["name"]: c for c in manifest["configs"]}[conf["name"]]
    assert entry["source"] == conf["source"]
    assert entry["reduced"] == conf["reduced"]
    agrees(conf)
    agrees(harness.Cell(manifest, CELL, rehearse=True).config)
    assert cell.entry["chips"] == 1
    assert CELL in next(m for m in manifest["end_to_end"]
                        if m["name"] == "train_tokens_per_s")["workloads"]


def test_the_configuration_against_the_catalog(manifest):
    """Every key of the catalog row's `config` is in the file under the same
    key with the same value, nested groups whole, but for `reduced`."""
    if not os.path.isfile(CATALOG):
        pytest.skip("the model catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "K-EXAONE-236B-A23B")
    conf = harness.Cell(manifest, CELL).config
    assert conf["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if conf.get(k) != v}
    assert changed == set(conf["reduced"])
    for key, (published, _here) in REDUCED.items():
        assert row["config"][key] == published


def test_the_cell_reports_what_the_issue_names(manifest):
    names = {m["name"] for m in harness.Cell(manifest, CELL).per_layer}
    # the readers a model shares stand under one name, this cell in their
    # lists (PR 50 folded the tags that named a cell: `<reader>.kex`)
    assert names >= {
        "compiles_in_window", "backend_start_s", "step_device_ms",
        "train_mfu", "device_idle_share.train", "fused_attn_share",
        "grouped_matmul_share", "attn_full_device_ms",
        "attn_window_device_ms", "attn_window_roofline", "attn_full_roofline",
        "held_moe_device_ms", "shared_expert_device_ms", "dense_ffn_device_ms",
        "held_experts_roofline", "held_slot_share", "expert_rows_fill",
        "kinds_head_loss_device_ms", "held_dispatch_device_ms"}
    assert not names & {"attn_device_ms", "moe_device_ms"}
    assert [w["chips"] for w in manifest["workloads"]].count(4) == 1
    for _spec, mod in harness.Cell(manifest, CELL).readers():
        assert hasattr(mod, "read")


def test_the_accepted_metrics_stand(manifest):
    """`grouped_matmul_share` (PR 29) reads as it read, OLMoE's cell and
    this one among its cells and the flagship not; the four readers of the
    window / full split read this cell. By name and as subsets: no append
    and no fold falsifies it."""
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    spec = dict(by_name["grouped_matmul_share"])
    cells = spec.pop("workloads")
    assert spec == {
        "name": "grouped_matmul_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "train step",
        "moves": "train_tokens_per_s"}
    assert {"olmoe-1b-7b-1c.lm-step-b2s4096", CELL} <= set(cells)
    assert "flagship-d1024-1c.step-b8s1024" not in cells
    for name in ("attn_full_device_ms", "attn_window_device_ms",
                 "attn_window_roofline", "attn_full_roofline"):
        assert CELL in by_name[name]["workloads"], name


def test_flops_against_a_hand_count(manifest):
    model = harness.Cell(manifest, CELL).config["model"]
    t = 8192
    proj = 2 * t * 6144 * (64 + 16) * 128 + 2 * t * 8192 * 6144
    full = proj + 4 * t * t * 64 * 128          # QK and PV, the whole matrix
    window = proj + 4 * t * 128 * 64 * 128      # 128 keys a query
    dense = 2 * t * 3 * 6144 * 18432
    shared = 2 * t * 3 * 6144 * 2048
    router = 2 * t * 6144 * 128
    rows = t * 8 * 8 / 128                      # 4096 of 65536 slots, balanced
    experts = 2 * rows * 3 * 6144 * 2048
    head = 2 * t * 6144 * 19200
    want = 3 * (4 * window + full + dense + 4 * (shared + router + experts)
                + head)
    assert lm_kinds_flops.flops_per_step(model, 1, t) == want
    assert lm_kinds_flops.held_expert_flops(model, rows) == 3 * experts
    assert lm_kinds_flops.flops_per_step(model, 1, t, held_rows=2 * rows) \
        == want + 3 * 4 * experts
    assert 68e12 < want < 69e12         # ISSUE 30: about 68 T a step
    # the kernel as executed: 136 of 256 pairs of 512-wide blocks a head in a
    # full layer, 31 under a window of 128; 2 products forward, 5 backward
    pair = 2 * 512 * 512 * 128
    k = lm_kinds_flops.attn_kernel_flops(model, 1, t, 0, (512, 512))
    assert k == {"fwd": 2 * pair * 64 * 136, "bwd": 5 * pair * 64 * 136}
    k = lm_kinds_flops.attn_kernel_flops(model, 1, t, 128, (512, 512))
    assert k == {"fwd": 2 * pair * 64 * 31, "bwd": 5 * pair * 64 * 31}
    assert lm_kinds_flops.visited_pairs(t, 128, 128, 128) == 127
    assert lm_kinds_flops.layer_kinds(model) == [
        (128, False), (128, True), (128, True), (0, True), (128, True)]
    # a uniform model's count is lm_flops' (the flagship's shape)
    from yardstick import lm_flops
    plain = {"vocab": 10, "d_model": 4, "n_heads": 2, "n_layers": 2,
             "d_ff": 8}
    assert lm_kinds_flops.flops_per_step(plain, 1, 2) == \
        lm_flops.flops_per_step(plain, 1, 2)


def test_the_flop_count_walks_the_blocks_the_kernel_walks():
    from tpu_mpi.xla import pallas_kernels as pk
    for t, bq, bk, window in [(8192, 512, 512, 0), (8192, 512, 512, 128),
                              (8192, 256, 256, 128), (2048, 128, 128, 300),
                              (1024, 512, 256, 128)]:
        assert lm_kinds_flops.visited_pairs(t, bq, bk, window) == \
            pk.causal_attention_walk(t, bq, bk, window)[2]


KINDS = [(128, False), (128, True), (128, True), (0, True), (128, True)]


@pytest.mark.parametrize("op_name, scope", [
    ("jit(local_step)/jvp(layer_2)/jit(block)/mlp/router/top_k", "router"),
    ("jit(local_step)/transpose(jvp(layer_1))/jit(block)/mlp/combine/"
     "scatter-add", "combine"),
    ("jit(local_step)/jvp(layer_1)/mlp/dispatch/sort", "dispatch"),
    ("jit(local_step)/transpose(jvp(layer_3))/mlp/experts/jit(backward)/"
     "grouped_matmul_dlhs", "experts"),
    ("jit(local_step)/jvp(layer_4)/mlp/shared/dot_general", "shared"),
    ("jit(local_step)/jvp(layer_0)/mlp/dense/dot_general", "dense"),
    ("jit(local_step)/transpose(jvp(layer_0))/mlp/checkpoint/"
     "rematted_computation/dense/mul", "dense"),
    ("jit(local_step)/jvp(layer_0)/mlp/mul", "mlp_rest"),
    ("jit(local_step)/jvp(layer_3)/attn/jit(attend)/causal_attention_fwd",
     "attn_full"),
    ("jit(local_step)/transpose(jvp(layer_2))/attn/jit(attend)/"
     "causal_attention_bwd", "attn_window"),
    ("jit(local_step)/jvp(layer_0)/attn/dot_general", "attn_window"),
    ("jit(local_step)/jvp(head_loss)/jit(take_along_axis)/gather",
     "head_loss"),
    ("jit(local_step)/optimizer/sub", "optimizer"),
    ("jit(local_step)/jvp(embed)/gather", "embed"),
    ("reduce_sum", kinds_scope_reduce.REST),
    ("params['layers'][3]['w_out']", "experts"),
    ("params['layers'][0]['w_out']", "dense"),
    ("params['layers'][3]['w_q']", "attn_full"),
    ("params['layers'][1]['w_k']", "attn_window"),
    ("params['layers'][2]['w_shared_in']", "shared"),
    ("params['lm_head']", "head_loss"),
])
def test_scope_rules(op_name, scope):
    assert kinds_scope_reduce.scope_of(op_name, KINDS) == scope
    assert scope in kinds_scope_reduce.SCOPES


def test_the_kernels_calls_are_told_apart_by_kind_and_direction():
    of = kinds_scope_reduce.kernel_of
    assert of("jit(s)/jvp(layer_3)/attn/jit(attend)/causal_attention_fwd",
              KINDS) == ("full", "fwd")
    assert of("jit(s)/transpose(jvp(layer_4))/attn/jit(attend)/"
              "causal_attention_bwd", KINDS) == ("window", "bwd")
    assert of("jit(s)/jvp(layer_3)/attn/dot_general", KINDS) is None


def test_the_share_matches_its_plain_reference():
    run = rehearse(CELL, seconds=0.5)
    r = run.results
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 2
    assert run.values["compiles_in_window"] == 0
    assert run.facts["flops_per_step"] > \
        run.facts["held_expert_flops_per_step"] > 0
    # nothing dropped: 32 tokens x 4 experts per token in each of 4 sparse
    # layers, and the held experts computed every slot routed to them
    for when in ("begin", "end"):
        held = run.facts["held"][when]
        assert held["slots"] == [128] * 4
        assert held["computed"] == held["held"]
        assert held["gathered"] == [128] * 4 and held["fallbacks"] == [0] * 4
    assert 0.0 < run.values["held_slot_share"] < 100.0
    assert run.values["expert_rows_fill"] == run.values["held_slot_share"]
    assert set(run.facts["attention"]) == {"window", "full"}
    assert run.facts["attention"]["window"]["layers"] == [0, 1, 2, 4]
    # no trace on the CPU: the scope readers report nothing and do not raise
    for name in ("step_device_ms", "attn_full_device_ms",
                 "attn_window_device_ms", "attn_window_roofline",
                 "attn_full_roofline", "held_moe_device_ms",
                 "shared_expert_device_ms", "dense_ffn_device_ms",
                 "held_experts_roofline", "kinds_head_loss_device_ms",
                 "held_dispatch_device_ms"):
        assert run.values[name] is None


def test_a_wrong_loss_or_a_lost_slot_is_not_correct(monkeypatch):
    manifest = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    ref = harness.Cell(manifest, CELL).reference()
    honest = ref.make_loss_from

    def off(model, d_loss, d_logits):
        def loss_from(*a):
            loss, logits = honest(model)(*a)
            return loss + d_loss, logits * (1.0 + d_logits)
        return loss_from
    monkeypatch.setattr(ref, "make_loss_from", lambda m: off(m, 1e-3, 0.0))
    assert not rehearse(CELL, seconds=0.2).results["correct"]
    monkeypatch.setattr(ref, "make_loss_from", lambda m: off(m, 0.0, 1e-3))
    assert not rehearse(CELL, seconds=0.2).results["correct"]
    monkeypatch.setattr(ref, "make_loss_from", honest)
    from tpu_mpi.models import transformer
    counts = transformer.transformer_held_counts

    def one_row_short(*a):
        slots, did = counts(*a)
        return slots, did.at[0, 0].add(-1)
    monkeypatch.setattr(transformer, "transformer_held_counts", one_row_short)
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
            def __init__(self, *args):
                self.args = args

            def compile(self):
                def bad(p, tok, lab):
                    new, loss = step(p, tok, lab)
                    return jax.tree.map(lambda a, b: b + 0.2 * (b - a),
                                        p, new), loss
                return bad

        class Step:
            lower = staticmethod(lambda *args: Lowered(*args))
        return Step, specs
    monkeypatch.setattr(
        transformer, "transformer_train_step",
        lambda cfg, mesh, lr, donate: other_lr(cfg, mesh, lr=lr, donate=False))
    assert not rehearse(CELL, seconds=0.2).results["correct"]


# the worst readings of a sound step on the chip, by leaf class (the file's
# `update_tolerance_why`), and a reading that the router's limit alone
# would let through
@pytest.mark.parametrize("leaf, sound, unsound", [
    ("gate", 0.429, 0.95), ("gate_proj", 0.257, 0.8), ("up_proj", 0.253, 0.8),
    ("down_proj", 0.251, 0.8), ("q_proj", 0.114, 0.4), ("k_proj", 0.116, 0.4),
    ("v_proj", 0.075, 0.3), ("o_proj", 0.066, 0.3), ("lm_head", 0.033, 0.3),
    ("embed_tokens", 0.072, 0.3), ("shared_down_proj", 0.070, 0.3),
    ("input_layernorm", 0.0, 0.3)])
def test_each_leaf_is_held_to_its_own_update_limit(manifest, leaf, sound,
                                                   unsound):
    cell = harness.Cell(manifest, CELL)
    limits = cell.generator().update_limits(
        {leaf: sound}, cell.config["update_tolerance"])
    assert set(limits) == {leaf}
    assert 2 * sound <= limits[leaf] < unsound <= 0.95
    assert limits[leaf] <= 0.9          # 1.0 is what no update at all reads
