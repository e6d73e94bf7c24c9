"""The manifest's per-layer entries, one case an entry so that each counts:
what `yardstick/README.md` says of an entry (a model gets no tag, a cell
joins the reader's `workloads`; a tag is for a second end-to-end metric
only), held by name and as subsets, never by an entry's place in the list.
And that no fold or append loses a reading: every (cell, reader) pair that
the manifest of commit a36c9e9 (PR 49) reported is still reported.

ISSUE 50 asked for this file under `tests/` (tier-1); a `benchmark` PR adds
files under the benchmark's own directories alone, so it stands here, and a
later PR of another kind may collect it from `tests/` with one import."""

import os

import pytest

from yardstick import harness

MANIFEST = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in MANIFEST["workloads"]]
MODEL_TAGS = {"moe", "kex", "pgu", "gra", "phi"}   # folded by PR 50


def reports(cell: str) -> set:
    """The end-to-end metrics a cell reports."""
    return {m["name"] for m in MANIFEST["end_to_end"]
            if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("spec", MANIFEST["per_layer"],
                         ids=[m["name"] for m in MANIFEST["per_layer"]])
def test_an_entry(spec):
    name = spec["name"]
    assert [m["name"] for m in MANIFEST["per_layer"]].count(name) == 1
    reader, _, tag = name.partition(".")
    mod = harness.load_module(
        os.path.join(harness.HERE, "layer_metrics", reader + ".py"),
        "ys_layer_" + reader)
    assert callable(mod.read)
    assert spec["moves"] in {m["name"] for m in MANIFEST["end_to_end"]}
    cells = spec.get("workloads", CELLS)
    assert len(set(cells)) == len(cells) and set(cells) <= set(CELLS)
    for cell in cells:
        assert spec["moves"] in reports(cell), cell
    # a tag sets a reader beside a second END-TO-END metric, never a model
    assert tag not in MODEL_TAGS
    if tag:
        twins = [m for m in MANIFEST["per_layer"] if m is not spec
                 and m["name"].partition(".")[0] == reader]
        assert twins and all(m["moves"] != spec["moves"] for m in twins)


def test_per_layer_has_room():
    assert len(MANIFEST["per_layer"]) <= 128


# cell -> the readers whose readings its traced run's line carried
REPORTED_AT_A36C9E9 = {
    "osu-allreduce-4r1c.large-reuse": """
        compiles_in_window backend_start_s armed_share
        rendezvous_wait_share host_overhead_us fold_roofline
        device_idle_share fold_dispatch_us idle_attributed_share
        arming_s ingraph_fold_share build_trace_s build_lower_s
        build_compile_s build_cache_misses""",
    "osu-allreduce-4r1c.small-reuse": """
        compiles_in_window backend_start_s armed_share
        rendezvous_wait_share coll_latency_tail host_overhead_us
        device_idle_share front_door_us rendezvous_skew_us
        rendezvous_wake_us fold_dispatch_us copyout_us
        op_span_coverage idle_attributed_share arming_s build_trace_s
        build_lower_s build_compile_s build_cache_misses""",
    "osu-allreduce-4r4c.large-reuse": """
        compiles_in_window backend_start_s armed_share
        rendezvous_wait_share ingraph_psum_algbw fold_dispatch_us
        arming_s xchip_bytes_per_op xchip_copy_out_ms
        ingraph_fold_share build_trace_s build_lower_s build_compile_s
        build_cache_misses""",
    "flagship-d1024-1c.step-b8s1024": """
        compiles_in_window backend_start_s step_device_ms train_mfu
        device_idle_share attn_device_ms head_loss_device_ms
        fused_attn_share build_trace_s build_lower_s build_compile_s
        build_cache_misses step_build_s kernel_traces
        blocked_head_share""",
    "olmoe-1b-7b-1c.lm-step-b2s4096": """
        compiles_in_window backend_start_s moe_device_ms
        moe_dispatch_device_ms moe_experts_roofline
        expert_load_max_over_mean step_device_ms train_mfu
        device_idle_share moe_attn_device_ms moe_head_loss_device_ms
        fused_attn_share grouped_matmul_share build_trace_s
        build_lower_s build_compile_s build_cache_misses step_build_s
        kernel_traces blocked_head_share""",
    "k-exaone-236b-a23b-1c.lm-step-b1s8192": """
        compiles_in_window backend_start_s step_device_ms train_mfu
        device_idle_share fused_attn_share grouped_matmul_share
        attn_full_device_ms attn_window_device_ms attn_window_roofline
        attn_full_roofline held_moe_device_ms shared_expert_device_ms
        dense_ffn_device_ms held_experts_roofline held_slot_share
        expert_rows_fill kinds_head_loss_device_ms
        held_dispatch_device_ms row_sum_product_share embed_device_ms
        build_trace_s build_lower_s build_compile_s build_cache_misses
        step_build_s kernel_traces blocked_head_share""",
    "openpangu-ultra-moe-718b-1c.lm-step-b1s4096": """
        compiles_in_window backend_start_s step_device_ms train_mfu
        device_idle_share fused_attn_share grouped_matmul_share
        latent_attn_device_ms latent_proj_device_ms
        latent_kernel_roofline norm_out_device_ms held_moe_device_ms
        dense_ffn_device_ms shared_expert_device_ms
        kinds_head_loss_device_ms held_slot_share expert_rows_fill
        held_dispatch_device_ms row_sum_product_share embed_device_ms
        build_trace_s build_lower_s build_compile_s build_cache_misses
        step_build_s kernel_traces blocked_head_share""",
    "granite-4.0-h-micro-1c.ssm-step-b1s8192": """
        compiles_in_window backend_start_s ssm_mixer_device_ms
        ssm_scan_device_ms ssm_conv_device_ms ssm_scan_roofline
        step_device_ms train_mfu device_idle_share fused_attn_share
        dense_ffn_device_ms kinds_head_loss_device_ms embed_device_ms
        row_sum_product_share step_build_s kernel_traces
        scan_kernel_share blocked_head_share""",
    "phi-4-mini-flash-reasoning-1c.sambay-step-b1s8192": """
        compiles_in_window backend_start_s sel_mixer_device_ms
        sel_scan_device_ms sel_scan_roofline gmu_device_ms
        diff_attn_device_ms diff_extra_device_ms
        shared_kv_attn_device_ms step_device_ms train_mfu
        device_idle_share fused_attn_share ssm_conv_device_ms
        dense_ffn_device_ms kinds_head_loss_device_ms embed_device_ms
        row_sum_product_share step_build_s kernel_traces build_trace_s
        build_lower_s build_compile_s build_cache_misses
        sel_scan_kernel_share blocked_head_share""",
    "qwen3-next-80b-a3b-1c.gdn-step-b1s8192": """
        compiles_in_window backend_start_s step_device_ms train_mfu
        device_idle_share fused_attn_share grouped_matmul_share
        held_moe_device_ms shared_expert_device_ms
        held_experts_roofline held_slot_share expert_rows_fill
        kinds_head_loss_device_ms held_dispatch_device_ms
        row_sum_product_share embed_device_ms build_trace_s
        build_lower_s build_compile_s build_cache_misses step_build_s
        kernel_traces ssm_conv_device_ms blocked_head_share
        gdn_mixer_device_ms gdn_scan_device_ms gdn_scan_roofline
        gated_attn_device_ms delta_chunked_share""",
    "kimi-linear-48b-a3b-1c.kda-step-b1s8192": """
        compiles_in_window backend_start_s step_device_ms train_mfu
        device_idle_share fused_attn_share grouped_matmul_share
        held_moe_device_ms shared_expert_device_ms dense_ffn_device_ms
        held_experts_roofline held_slot_share expert_rows_fill
        kinds_head_loss_device_ms held_dispatch_device_ms
        latent_attn_device_ms latent_proj_device_ms
        latent_kernel_roofline row_sum_product_share embed_device_ms
        build_trace_s build_lower_s build_compile_s build_cache_misses
        step_build_s kernel_traces ssm_conv_device_ms
        blocked_head_share delta_chunked_share""",
}

def test_every_reading_of_a36c9e9_is_still_reported():
    for cell, readers in REPORTED_AT_A36C9E9.items():
        now = [m["name"].partition(".")[0]
               for m in harness.Cell(MANIFEST, cell).per_layer]
        assert len(set(now)) == len(now), cell      # each reader once a cell
        assert set(readers.split()) <= set(now), cell
