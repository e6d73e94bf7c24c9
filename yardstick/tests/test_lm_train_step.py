"""The `lm_train_step` kind (the sparse-expert language model's cell): the
configuration file against the catalog's keys and against the block the
program is handed, the FLOP count against a hand count, the scope rules on
op names read off the v5e's compiled step, and the cell rehearsed at tiny
size against its plain reference. Correctness only; nothing is measured."""

import json
import os

import jax
import pytest

from yardstick import harness, lm_flops, moe_scope_reduce
from test_generators import rehearse, run_py

OLMOE = "olmoe-1b-7b-1c.lm-step-b2s4096"
# the catalog row of OLMoE-1B-7B-0125-Instruct (model-configs guide): every
# key of its `config`, as published
PUBLISHED = {
    "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 1024,
    "max_position_embeddings": 4096, "model_type": "olmoe",
    "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 16,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304}


@pytest.fixture(scope="module")
def manifest():
    return harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))


def agrees(conf: dict) -> None:
    """The block handed to the program says what the published keys say."""
    m = conf["model"]
    assert m["d_model"] == conf["hidden_size"]
    assert m["d_ff"] == conf["intermediate_size"]
    assert m["n_heads"] == conf["num_attention_heads"] \
        == conf["num_key_value_heads"]        # plain multi-head attention
    assert m["n_layers"] == conf["num_hidden_layers"]
    assert m["n_experts"] == conf["num_experts"]
    assert m["experts_per_tok"] == conf["num_experts_per_tok"]
    assert m["vocab"] == conf["vocab_size"]
    assert m["norm_eps"] == conf["rms_norm_eps"]
    assert conf["rope_theta"] == 10000 and conf["rope_scaling"] is None
    assert m["router_aux_coef"] == conf["router_aux_loss_coef"]
    assert m["tie_embeddings"] == conf["tie_word_embeddings"]
    assert conf["hidden_act"] == "silu"       # the program's gated experts
    assert m["qk_norm"] and conf["model_type"] == "olmoe"
    assert not conf["norm_topk_prob"] and conf["clip_qkv"] is None


def test_the_configuration_keeps_every_published_width(manifest):
    cell = harness.Cell(manifest, OLMOE)
    conf = cell.config
    assert conf["kind"] == cell.traffic["kind"] == "lm_train_step"
    changed = {k for k, v in PUBLISHED.items() if conf[k] != v}
    assert changed == set(conf["reduced"]) == {"num_hidden_layers"}
    assert conf["num_hidden_layers"] == 4
    agrees(conf)
    assert cell.traffic["seq"] == conf["max_position_embeddings"]
    assert set(conf["assumed"]) >= {"optimizer", "router_aux_loss_coef",
                                    "z_loss", "weights", "tokens"}
    assert conf["compare_steps"] == 3 and "dropped" in conf["guarantees"]
    entry = {c["name"]: c for c in manifest["configs"]}["olmoe-1b-7b-1c"]
    assert entry["source"] == conf["source"] and \
        entry["source"].startswith("https://huggingface.co/allenai/OLMoE")
    agrees(harness.Cell(manifest, OLMOE, rehearse=True).config)


def test_new_cells_report_what_the_issue_names(manifest):
    olmoe = {m["name"] for m in harness.Cell(manifest, OLMOE).per_layer}
    assert olmoe >= {"moe_device_ms", "moe_dispatch_device_ms",
                     "moe_experts_roofline", "expert_load_max_over_mean",
                     "step_device_ms", "train_mfu",
                     "device_idle_share.train", "compiles_in_window",
                     "moe_attn_device_ms", "moe_head_loss_device_ms"}
    assert not olmoe & {"attn_device_ms", "head_loss_device_ms"}
    assert [w["chips"] for w in manifest["workloads"]].count(4) == 1


def test_flops_against_a_hand_count(manifest):
    model = harness.Cell(manifest, OLMOE).config["model"]
    tokens = 2 * 4096
    attn = 2 * tokens * 2048 * 6144 + 4 * 2 * 4096 * 4096 * 2048 \
        + 2 * tokens * 2048 * 2048
    experts = 2 * (tokens * 8) * 3 * 2048 * 1024        # 8 of 64 experts
    router = 2 * tokens * 2048 * 64
    head = 2 * tokens * 2048 * 50304
    want = 3 * (4 * (attn + experts + router) + head)
    assert lm_flops.flops_per_step(model, 2, 4096) == want
    assert lm_flops.expert_flops_per_layer(model, 2, 4096) == 3 * experts
    assert 21.5e12 < want < 21.7e12         # ISSUE 25: 21.6 TFLOP a step
    dense = {"vocab": 10, "d_model": 4, "n_layers": 1, "d_ff": 8}
    assert lm_flops.flops_per_step(dense, 1, 2) == 3 * (
        2 * 2 * 4 * 12 + 4 * 1 * 2 * 2 * 4 + 2 * 2 * 4 * 4
        + 2 * 2 * 2 * 4 * 8 + 2 * 2 * 4 * 10)


@pytest.mark.parametrize("instruction, op_name, scope", [
    ("fusion.7", "jit(local_step)/jvp(layer_2)/mlp/router/top_k", "router"),
    ("fusion.8", "jit(local_step)/transpose(jvp(layer_0))/mlp/combine/gather",
     "combine"),
    ("sort.3", "jit(local_step)/jvp(layer_1)/mlp/dispatch/sort", "dispatch"),
    ("fusion.9", "jit(local_step)/transpose(jvp(layer_3))/mlp/experts/mul",
     "experts"),
    ("ragged-dot-none.21", "ragged-dot-none", "experts"),
    ("fusion.1", "jit(local_step)/jvp(layer_0)/mlp/mul", "mlp_rest"),
    ("fusion.2", "jit(local_step)/transpose(jvp(layer_1))/attn/jvp(layer_1)"
     "/attn/checkpoint/rematted_computation/mul", "attn"),
    ("fusion.3", "jit(local_step)/jvp(head_loss)/jit(take_along_axis)/gather",
     "head_loss"),
    ("fusion.4", "jit(local_step)/jvp(aux_loss)/mul", "aux_loss"),
    ("fusion.5", "jit(local_step)/optimizer/sub", "optimizer"),
    ("fusion.6", "jit(local_step)/jvp(embed)/gather", "embed"),
    ("reduce.1", "reduce_sum", moe_scope_reduce.REST),
    ("copy.345", "params['layers'][3]['w_out']", "experts"),
    ("copy.9", "params['layers'][0]['w_qkv']", "attn"),
    ("copy.1", "params['lm_head']", "head_loss"),
])
def test_scope_rules(instruction, op_name, scope):
    assert moe_scope_reduce.scope_of(instruction, op_name) == scope
    assert scope in moe_scope_reduce.SCOPES


def test_scopes_of_an_hlo_text():
    text = ('  %fusion.1 = f32[2]{0} fusion(%a), kind=kLoop, metadata={'
            'op_name="jit(s)/jvp(layer_0)/mlp/dispatch/gather"}\n'
            '  %ragged-dot-none.2 = bf16[4,4]{1,0} custom-call(%b), '
            'metadata={op_name="ragged-dot-none"}\n'
            '  %copy.3 = f32[2]{0} copy(%c)\n')
    assert moe_scope_reduce.scopes_of_hlo(text) == {
        "fusion.1": "dispatch", "ragged-dot-none.2": "experts",
        "copy.3": moe_scope_reduce.REST}


def test_the_expert_step_matches_its_plain_reference():
    run = rehearse(OLMOE, seconds=0.5)
    r = run.results
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 2
    assert run.values["compiles_in_window"] == 0
    assert run.facts["flops_per_step"] > run.facts["expert_flops_per_step"] > 0
    # nothing dropped: 2 x 32 tokens x 2 experts per token in each of 2 layers
    for when in ("begin", "end"):
        assert [sum(layer) for layer in run.facts["expert_counts"][when]] \
            == [128, 128]
    assert run.values["expert_load_max_over_mean"] >= 1.0
    # no trace on the CPU: the scope readers report nothing and do not raise
    for name in ("moe_device_ms", "moe_dispatch_device_ms",
                 "moe_experts_roofline", "step_device_ms",
                 "moe_attn_device_ms", "moe_head_loss_device_ms"):
        assert run.values[name] is None


def test_a_wrong_loss_or_a_lost_slot_is_not_correct(monkeypatch):
    import jax.numpy as jnp
    manifest = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    ref = harness.Cell(manifest, OLMOE).reference()
    honest = ref.make_loss_from

    def off(model, d_loss, d_logits):
        def loss_from(*a):
            loss, logits = honest(model)(*a)
            return loss + d_loss, logits * (1.0 + d_logits)
        return loss_from
    monkeypatch.setattr(ref, "make_loss_from", lambda m: off(m, 1e-3, 0.0))
    assert not rehearse(OLMOE, seconds=0.2).results["correct"]
    monkeypatch.setattr(ref, "make_loss_from", lambda m: off(m, 0.0, 1e-3))
    assert not rehearse(OLMOE, seconds=0.2).results["correct"]
    monkeypatch.setattr(ref, "make_loss_from", honest)
    from tpu_mpi.models import transformer
    counts = transformer.transformer_expert_counts
    monkeypatch.setattr(transformer, "transformer_expert_counts",
                        lambda *a: counts(*a).at[0, 0].add(-1))
    assert not rehearse(OLMOE, seconds=0.2).results["correct"]


def _other_lr(step):
    """The step at 1.2 x its learning rate: every parameter moves on by a
    fifth of its update again."""
    def bad(p, tok, lab):
        new, loss = step(p, tok, lab)
        return jax.tree.map(lambda a, b: b + 0.2 * (b - a), p, new), loss
    return bad


def _no_update(step):
    return lambda p, tok, lab: (p, step(p, tok, lab)[1])


def _one_leaf_left(step):
    """The experts' `w_gate` of the last layer never gets its gradient."""
    def bad(p, tok, lab):
        new, loss = step(p, tok, lab)
        new["layers"][-1]["w_gate"] = p["layers"][-1]["w_gate"]
        return new, loss
    return bad


def _one_expert_left(step):
    """One expert of eight in one layer never gets its `w_out` gradient: an
    eighth of that leaf's update, or less, is missing."""
    def bad(p, tok, lab):
        new, loss = step(p, tok, lab)
        new["layers"][0]["w_out"] = new["layers"][0]["w_out"].at[3].set(
            p["layers"][0]["w_out"][3])
        return new, loss
    return bad


@pytest.mark.parametrize("plant", [_other_lr, _no_update, _one_leaf_left,
                                   _one_expert_left])
def test_a_wrong_update_is_not_correct(monkeypatch, plant):
    # the losses and the logits of a step with a wrong update are right (the
    # reference computes them from the system's own parameters): only
    # holding the update to the reference's gradient sees it
    from tpu_mpi.models import transformer
    honest = transformer.transformer_train_step

    def planted(model, mesh, lr, **kw):
        step, specs = honest(model, mesh, lr=lr)
        return jax.jit(plant(step)), specs
    monkeypatch.setattr(transformer, "transformer_train_step", planted)
    run = rehearse(OLMOE, seconds=0.2)
    assert not run.results["correct"] and run.results["failed"] == 0


def test_cpu_rehearsal_through_run_py():
    p = run_py("--workload", OLMOE, "--seed", "4100000123", "--seconds", "0.5",
               "--trace", "1", "--rehearse-cpu")
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.splitlines()[-1])
    assert last["correct"] and last["failed"] == 0
    assert set(last["metrics"]) == {"compiles_in_window",
                                    "expert_load_max_over_mean"}
