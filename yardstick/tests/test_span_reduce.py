"""The span readers on hand-made span lists and profiles: the parts add up,
an idle gap goes to the thread that caused it, a clock that cannot be
aligned silences every reader, and the CPU rehearsal reports the one exact
count among them. Nothing here is a timing."""

import json
import os
import subprocess
import sys

import pytest

from yardstick import harness, scope_reduce, span_reduce as sr

OFFSET_NS = 5_000_000_000.0     # profiler ns = monotonic ns + this
US = 1e-6


def load(reader):
    return harness.load_module(
        os.path.join(harness.HERE, "layer_metrics", reader + ".py"),
        "ys_layer_" + reader)


def span(sid, parent, name, t0, t1, **extra):
    return {"trace": "t", "span": sid, "parent": parent, "name": name,
            "who": "rank ?", "t0": t0, "t1": t1, "status": "ok", **extra}


def op_tree(rnd, rank, t0, last, *, nbytes=8, door=20, lock=2, skew=100,
            fold=50, wake=30, copy=10, open_us=5, cid=0):
    """One rank's op of round `rnd`, starting at `t0` seconds; durations in
    microseconds. The last arriver dispatches the fold, the others wait."""
    oid = f"c{cid}r{rnd}k{rank}"
    at, out = t0, []

    def child(name, us, parent=oid, **extra):
        nonlocal at
        out.append(span(f"{oid}.{len(out)}", parent, name, at, at + us * US,
                        cid=cid, round=rnd, rank=rank, **extra))
        at += us * US
    child("front_door", door)
    child("lock", lock)
    if last:
        child("fold_dispatch", fold)
        out.append(span(f"{oid}.c", out[-1]["span"], "colocate",
                        out[-1]["t0"], out[-1]["t0"] + 5 * US,
                        bytes_moved=0, copies=0))
    else:
        wait0 = at
        wait = span(f"{oid}.w", oid, "rendezvous", wait0, wait0)
        out.append(wait)
        child("rdv_skew", skew, wait["span"])
        child("rdv_fold", fold, wait["span"])
        child("rdv_wake", wake, wait["span"])
        wait["t1"] = at
    child("copyout", copy)
    at += open_us * US
    op = span(oid, None, "op", t0, at, coll="allreduce", cid=cid, round=rnd,
              rank=rank, nbytes=nbytes, lane="armed", last=last,
              t_ann=t0 + (door - 1) * US)
    return [op] + out


def annotation(op, late_us=1.0):
    """The profiler's side of an op: (start ns, mono_ns, key). The program
    opens it at the channel's door, between a clock read (`mono_ns`, here
    2 us before the op's `t_ann`) and a second one (`t_ann`)."""
    mono_ns = (op["t_ann"] - 2 * US) * 1e9
    return (mono_ns + OFFSET_NS + late_us * 1e3, mono_ns,
            (str(op["cid"]), op["round"], op["rank"]))


def rounds(n_rounds, t0=10.0, period=400 * US, **kw):
    spans = []
    for rnd in range(n_rounds):
        for rank in range(4):
            spans += op_tree(rnd, rank, t0 + rnd * period + rank * US,
                             last=(rank == rnd % 4), **kw)
    return spans


def profile_of(spans, lo_s=9.0, hi_s=11.0):
    prof = sr.Profile(window=(lo_s * 1e9 + OFFSET_NS, hi_s * 1e9 + OFFSET_NS))
    prof.annotations = [annotation(s) for s in spans if s["name"] == "op"]
    return prof


class FakeRun:
    """What the readers touch of harness.Run."""

    def __init__(self, summary=None, traffic=None):
        self.prepared = {sr.KEY: summary}
        self.facts = {"op": "allreduce", "ops": 0, "ranks": 4}
        self.traffic = traffic or {"counts": [1 << 20, 2], "dtype": "float32",
                                   "sync": ["per-block", "per-op"]}
        self.counters, self.rows = {}, []
        self.trace, self.traced, self.rehearse = None, {}, False

    def row(self, text):
        self.rows.append(text)


def test_the_parts_add_up_to_the_op_bracket():
    spans = rounds(6) + rounds(1, t0=10.5, nbytes=4 << 20, cid=1)   # a rung
    summary = sr.align(spans, profile_of(spans))
    assert summary is not None and len(summary.ops) == 28
    assert summary.offset_ns == pytest.approx(OFFSET_NS + 1e3)
    assert summary.residual_worst_ns < 2e3 and summary.outside_worst_ns == 0
    run = FakeRun(summary)
    assert sr.op_rung_bytes(run) == 8
    assert len(sr.sampled_ops(run)) == 24           # the 8 B rung alone
    # one rank in four dispatches the fold, three wait: per op span
    assert load("front_door_us").read(run) == pytest.approx(20.0)
    assert load("fold_dispatch_us").read(run) == pytest.approx(50.0 / 4)
    assert load("rendezvous_skew_us").read(run) == pytest.approx(75.0)
    assert load("rendezvous_wake_us").read(run) == pytest.approx(22.5)
    assert load("copyout_us").read(run) == pytest.approx(10.0)
    row = sr.parts_row(run)
    mean_op = 20 + 2 + 10 + 5 + (50 + 3 * 180) / 4
    assert row["op"] == pytest.approx(mean_op)
    assert sum(row[p] for p in sr.PARTS) + row["(open)"] \
        == pytest.approx(row["op"])
    assert row["(open)"] == pytest.approx(5.0)
    cover = load("op_span_coverage").read(run)
    assert cover == pytest.approx(100.0 * (mean_op - 5.0) / mean_op)
    assert any("op span parts" in r for r in run.rows)


def test_ops_outside_the_profiled_interval_are_left_out():
    spans = rounds(4, t0=10.0) + rounds(4, t0=20.0, cid=1)
    summary = sr.align(spans, profile_of(spans, 9.0, 11.0))
    assert len(summary.ops) == 16
    cut = sr.align(spans, profile_of(spans, 9.0, 11.0), kept_s=1.0 + 850e-6)
    assert len(cut.ops) == 8            # trace buffers dropped: the end goes


def test_a_clock_that_does_not_align_silences_every_reader():
    spans = rounds(6)
    prof = profile_of(spans)
    assert sr.align(spans, prof) is not None
    # the spans' clock runs 1 ms off the one their annotations carry
    shifted = [dict(s, t0=s["t0"] + 1e-3, t1=s["t1"] + 1e-3,
                    **({"t_ann": s["t_ann"] + 1e-3} if "t_ann" in s else {}))
               for s in spans]
    assert sr.align(shifted, prof) is None
    # one annotation far from its op is enough ...
    one = list(prof.annotations)
    s, m, k = one[5]
    one[5] = (s + 50_000.0, m, k)
    assert sr.align(spans, sr.Profile(prof.window, one)) is None
    # ... unless the op's own second clock read shows the thread was late
    late = [dict(sp, t_ann=sp["t_ann"] + 60 * US)
            if sp["name"] == "op" and (str(sp["cid"]), sp["round"],
                                       sp["rank"]) == k else sp
            for sp in spans]
    got = sr.align(late, sr.Profile(prof.window, one))
    assert got is not None and got.residual_worst_ns > 40_000
    assert got.outside_worst_ns == 0
    # no annotation at all, or no op inside the interval
    assert sr.align(spans, sr.Profile(prof.window, [])) is None
    assert sr.align(spans, profile_of(spans, 30.0, 31.0)) is None
    run = FakeRun(None)
    for reader in ("front_door_us", "rendezvous_skew_us", "rendezvous_wake_us",
                   "fold_dispatch_us", "copyout_us", "op_span_coverage",
                   "idle_attributed_share", "xchip_copy_out_ms"):
        assert load(reader).read(run) is None, reader


def test_an_idle_gap_goes_to_the_thread_that_launched_the_fold():
    # round 0: rank 0 arrives last; round 1: rank 1; round 2: rank 2; each
    # 400 us apart. Round 1 is one the program did not sample: no spans
    spans = [s for s in rounds(3) if "r1k" not in s["span"]]
    prof = profile_of(spans, 9.9999, 10.0015)
    summary = sr.align(spans, prof)
    to_ns = lambda s: s * 1e9 + summary.offset_ns
    last0, last2 = (next(r for r in summary.ops if r["op"]["last"]
                         and r["op"]["round"] == rnd) for rnd in (0, 2))
    f0, f2 = last0["spans"]["fold_dispatch"], last2["spans"]["fold_dispatch"]
    lo, hi = to_ns(summary.lo_s), to_ns(summary.hi_s)
    fold0 = (to_ns(f0["t1"]) + 10e3, to_ns(f0["t1"]) + 14e3)    # device events
    slice_ = (fold0[1] + 30e3, fold0[1] + 31e3)                 # a readback's
    fold1 = (fold0[0] + 400e3, fold0[1] + 400e3)    # of the round in between
    fold2 = (to_ns(f2["t1"]) + 10e3, to_ns(f2["t1"]) + 14e3)
    summary.fold_starts = [fold0, fold1, fold2]
    summary.gaps = [(lo, fold0[0]), (fold0[1], slice_[0]),
                    (slice_[1], fold1[0]), (fold1[1], fold2[0]),
                    (fold2[1], hi)]
    named, lags = sr.attribute_gaps(summary)
    # both folds started 10 us after their 50 us dispatch had returned
    assert lags == pytest.approx([60 * US, 60 * US])
    # gap 1 ends at round 0's fold: the last arriver's whole call names it
    before = (last0["t0"] - summary.lo_s)
    # gap 4 began while round 2's last arriver was not yet in its op
    outside4 = max(0.0, last2["t0"] - (fold1[1] - summary.offset_ns) / 1e9)
    assert outside4 > 0
    assert named["outside the program"] == pytest.approx(before + outside4)
    assert named["front_door"] == pytest.approx(2 * 20 * US)
    assert named["lock"] == pytest.approx(2 * 2 * US)
    assert named["fold_dispatch"] == pytest.approx(2 * 50 * US)
    assert named["launch"] == pytest.approx(2 * 10 * US)
    # gap 3 ends at a fold, but the latest sampled dispatch before it was
    # round 0's, whose own fold had started since: not round 0's to name
    assert named[sr.UNSAMPLED] == pytest.approx((fold1[0] - slice_[1]) / 1e9)
    # gaps 2 and 5 end at a readback's slice and at the interval's end
    assert named[sr.NOT_A_FOLD] == pytest.approx(
        (slice_[0] - fold0[1] + hi - fold2[1]) / 1e9)
    assert sum(named.values()) == pytest.approx(
        sum(e - s for s, e in summary.gaps) / 1e9)
    run = FakeRun(summary)
    share = load("idle_attributed_share").read(run)
    idle = sum(named.values())
    assert share == pytest.approx(100.0 * (idle - named[sr.NOT_A_FOLD])
                                  / idle)
    assert any("idle seconds" in r and "2 sampled rounds" in r
               for r in run.rows)
    # no sampled round's fold in the interval: nothing to say
    summary.gaps = [(slice_[1], fold1[0])]
    assert sr.attribute_gaps(summary) is None


def test_watcher_medians_per_round():
    spans = rounds(3, nbytes=4096)
    for rnd, (cin, fold, outs) in enumerate([(20, 25, (30, 32, 31)),
                                             (22, 28, (35, 30, 33)),
                                             (21, 27, (29, 34, 30))]):
        last = rnd % 4
        t0 = next(s["t0"] for s in spans if s["name"] == "fold_dispatch"
                  and s["round"] == rnd)
        oid = f"c0r{rnd}k{last}"
        spans.append(span(f"w{rnd}i", oid, "copy_in.done", t0, t0 + cin * 1e-3))
        spans.append(span(f"w{rnd}f", oid, "fold.done", t0, t0 + fold * 1e-3))
        others = [r for r in range(4) if r != last]
        for rank, ms in zip(others, outs):
            t0 = next(s["t0"] for s in spans if s["name"] == "copyout"
                      and s["round"] == rnd and s["rank"] == rank)
            spans.append(span(f"w{rnd}o{rank}", f"c0r{rnd}k{rank}",
                              "copy_out.done", t0, t0 + ms * 1e-3))
    summary = sr.align(spans, profile_of(spans))
    run = FakeRun(summary, {"counts": [1024], "dtype": "float32",
                            "sync": "per-block"})
    # completion to completion: the fold's output ready -> the last result
    # home. Dispatches differ by microseconds here, so (35, 34, 34) - fold
    assert load("xchip_copy_out_ms").read(run) == pytest.approx(7.0, abs=0.3)
    (row,) = [r for r in run.rows if r.startswith("watcher")]
    assert "3 stamped rounds" in row
    assert "-> fold's output ready 6.000" in row
    assert "dispatch -> operands on the folding chip 21.000" in row
    assert "the slowest 34.000" in row


def test_counters_read_as_deltas_and_stay_silent_without_the_program():
    reader, arming = load("xchip_bytes_per_op"), load("arming_s")
    run = FakeRun()
    run.facts["ops"] = 10
    snap = lambda b: {"comms": [{"xchip_bytes": b}, {"xchip_bytes": 2 * b}],
                      "arming_s": 2.125}
    run.counters = {"begin": snap(100), "end": snap(100 + 10 * 2048)}
    assert reader.read(run) == 3 * 2048 and isinstance(reader.read(run), int)
    assert reader.EXACT_COUNT
    assert arming.read(run) == 2.125
    # a program older than the counters: its snapshot has no such key
    run.counters = {"begin": {"comms": [{}]}, "end": {"comms": [{}]}}
    assert reader.read(run) is None and arming.read(run) is None


HLO = """
HloModule jit_local_step
%fused_computation.1 (p: f32[8]) -> f32[8] {
  %mul.9 = f32[8] multiply(%p, %p), metadata={op_name="jit(local_step)/jvp(layer_0)/attn/mul"}
}
ENTRY %main {
  %fusion.1 = bf16[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(local_step)/jvp(layer_0)/attn/mul" source_file="x.py" source_line=3}
  %fusion.2 = bf16[8]{0} fusion(%a), kind=kLoop, metadata={op_name="jit(local_step)/transpose(jvp(layer_7))/mlp/dot_general"}
  %fusion.3 = f32[8]{0} fusion(%a), kind=kLoop, metadata={op_name="jit(local_step)/transpose(jvp(head_loss))/jit(log_softmax)/sub"}
  %fusion.4 = f32[8]{0} fusion(%a), kind=kLoop, metadata={op_name="jit(local_step)/optimizer/sub"}
  %gather.5 = f32[8]{0} gather(%a), metadata={op_name="jit(local_step)/jvp(embed)/gather"}
  ROOT %copy.6 = f32[8]{0} copy(%a)
}
"""


def test_scopes_come_from_the_hlo_text_by_instruction_name():
    scopes = scope_reduce.scopes_of_hlo(HLO)
    assert scopes == {"mul.9": "attn", "fusion.1": "attn", "fusion.2": "mlp",
                      "fusion.3": "head_loss", "fusion.4": "optimizer",
                      "gather.5": "embed"}
    secs = scope_reduce.by_scope(
        {"fusion.1": [2, 0.5], "fusion.2": [1, 0.25], "fusion.3": [1, 1.0],
         "copy.6": [4, 0.125], "fusion.4": [1, 0.0625]}, scopes)
    assert secs == {"embed": 0.0, "attn": 0.5, "mlp": 0.25, "head_loss": 1.0,
                    "optimizer": 0.0625, "(unscoped)": 0.125}
    assert scope_reduce.scope_of("params['embed']") == "(unscoped)"
    # an op name the text lacks: the text is not that of the program traced
    ops = {"fusion.1": [2, 0.75], "copy.6": [4, 0.125], "fusion.9": [1, 0.125]}
    assert scope_reduce.absent_share(ops, HLO) == pytest.approx(0.125)
    assert scope_reduce.absent_share({"fusion.1": [2, 0.75]}, HLO) == 0.0
    run = FakeRun()
    run.traced_ops = lambda: 0.0
    assert load("attn_device_ms").read(run) is None
    assert load("head_loss_device_ms").read(run) is None


def test_the_rehearsal_reports_the_bytes_between_chips_as_an_exact_count():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("TPU_MPI_TRACE_SAMPLE", None)
    p = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"), "--workload",
         "osu-allreduce-4r4c.large-reuse", "--seed", "2147483659",
         "--seconds", "0.5", "--trace", "1", "--rehearse-cpu"],
        capture_output=True, text=True, env=env, cwd=harness.ROOT, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.splitlines()
    payload = 4096 * 4              # the traffic file's rehearsal size
    assert f"xchip_bytes_per_op: {6 * payload}" in lines
    assert "xchip_copy_out_ms: not measured" in lines
    last = json.loads(lines[-1])
    assert last["metrics"]["xchip_bytes_per_op"]["value"] == 6 * payload
    assert last["metrics"]["armed_share.large"]["value"] == 100.0
    assert set(last["metrics"]) == {"compiles_in_window", "armed_share.large",
                                    "xchip_bytes_per_op"}
