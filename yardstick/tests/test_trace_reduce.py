"""trace_reduce: the interval arithmetic on hand-made planes, and the whole
reduction on one small trace recorded on the v5e (fixtures/)."""

import os

import pytest

from yardstick import trace_reduce as tr

US = 1000.0     # planes are in nanoseconds


def planes():
    ops = [("fusion.1", 10 * US, 30 * US), ("fusion.1", 25 * US, 40 * US),
           ("copy.2", 60 * US, 70 * US), ("fusion.1", 95 * US, 120 * US)]
    mods = [("jit_plain_fold(123)", 10 * US, 40 * US),
            ("jit_plain_fold(123)", 60 * US, 70 * US)]
    quiet = [("fusion.9", 50 * US, 55 * US)]
    host = [("ys:traced", 0.0, 100 * US), ("ys:op", 0.0, 45 * US),
            ("ys:rebind", 41 * US, 44 * US), ("ys:barrier", 70 * US, 99 * US),
            ("PjitFunction(f)", 1 * US, 2 * US)]
    other = [("ys:op", 42 * US, 58 * US)]
    return [("/host:CPU", [("python", host), ("rank-1", other)]),
            ("/device:TPU:0", [("XLA Ops", ops), ("XLA Modules", mods),
                               ("Steps", [])]),
            ("/device:TPU:1", [("XLA Ops", quiet)]),
            ("/device:TPU:0 SparseCore", [("XLA Ops", ops)]),
            ("Task Environment", [])]


def test_union_clip_and_gaps():
    u = tr.union([(5, 7), (1, 3), (2, 4), (7, 9), (9, 9)])
    assert u == [(1, 4), (5, 9)]
    assert tr.total(u) == 7
    assert tr.clip(u, 2, 6) == [(2, 4), (5, 6)]
    assert tr.gaps(u, 0, 10) == [(0, 1), (4, 5), (9, 10)]


def test_busy_idle_and_per_module_time():
    s = tr.summarize_planes(planes())
    assert s.window_s == pytest.approx(100e-6)
    assert [c.ordinal for c in s.chips] == [0, 1]
    # chip 0: [10,40] + [60,70] + [95,100 clipped] = 45 us of 100
    assert s.busiest.ordinal == 0
    assert s.busiest.busy_s == pytest.approx(45e-6)
    assert s.busiest.idle_share == pytest.approx(0.55)
    assert s.chips[1].idle_share == pytest.approx(0.95)
    assert s.busy_mean_s(2) == pytest.approx((45e-6 + 5e-6) / 2)
    assert s.busy_mean_s(4) == pytest.approx((45e-6 + 5e-6) / 4)
    assert s.dropped_s == 0.0
    runs, secs = s.module_seconds("plain_fold")
    assert runs == 2 and secs == pytest.approx(40e-6)
    assert s.module_seconds("no_such_kernel") == (0, 0.0)
    # whole function names only: "fold" is not `jit_plain_fold`
    assert s.module_seconds("fold") == (0, 0.0)
    assert s.module_seconds("plain") == (0, 0.0)
    assert s.device_ops[0][0] == "fusion.1"
    assert s.device_ops[0][1] == pytest.approx(40e-6)   # 20 + 15 + 5 clipped
    assert s.marks["ys:op"][0] == 2


def test_gaps_are_named_by_what_the_host_did():
    s = tr.summarize_planes(planes())
    named = dict(s.idle_gaps)
    # [0,10] under ys:op; [40,60]: rank-1's ys:op covers 16 us of it, more
    # than rebind's 3; [70,95] under ys:barrier
    assert named["ys:op"] == pytest.approx(30e-6)
    assert named["ys:barrier"] == pytest.approx(25e-6)
    assert sum(named.values()) == pytest.approx(55e-6)


def test_the_interval_is_cut_where_trace_buffers_dropped():
    cut = planes()
    cut[1][1].append(("XLA TraceMe", [(tr.DROPPED, 65 * US, 130 * US)]))
    s = tr.summarize_planes(cut)
    assert s.window_s == pytest.approx(65e-6)
    assert s.dropped_s == pytest.approx(35e-6)
    # [10,40] + [60,65 cut]
    assert s.busiest.busy_s == pytest.approx(35e-6)
    assert s.busiest.idle_share == pytest.approx(1 - 35 / 65)


def test_a_trace_without_device_work_is_an_error():
    host_only = [p for p in planes() if not p[0].startswith("/device")]
    with pytest.raises(ValueError, match="no operation ran"):
        tr.summarize_planes(host_only)


def test_window_defaults_to_the_device_events():
    no_mark = [(n, [(ln, [e for e in evs if e[0] != "ys:traced"])
                    for ln, evs in lines]) for n, lines in planes()]
    s = tr.summarize_planes(no_mark)
    assert s.window_s == pytest.approx(110e-6)      # 10 us .. 120 us


FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "v5e-large-reuse.xplane.pb.gz")


@pytest.mark.skipif(not os.path.isfile(FIXTURE), reason="no recorded trace")
def test_recorded_trace_from_the_v5e():
    s = tr.summarize(FIXTURE)
    facts = __import__("json").load(open(FIXTURE.replace(".xplane.pb.gz",
                                                         ".json")))
    assert len(s.chips) == facts["chips"]
    assert s.window_s == pytest.approx(facts["window_s"], rel=1e-6)
    assert s.busiest.busy_s == pytest.approx(facts["busy_s"], rel=1e-6)
    runs, secs = s.module_seconds(*facts["fold_functions"])
    assert runs == facts["fold_runs"]
    assert secs / runs == pytest.approx(facts["fold_s_per_run"], rel=1e-6)
    assert s.idle_gaps and s.device_ops


def test_fold_roofline_refuses_a_trace_without_a_known_fold():
    from types import SimpleNamespace
    from yardstick import harness
    reader = harness.load_module(os.path.join(
        harness.HERE, "layer_metrics", "fold_roofline.py"),
        "ys_layer_fold_roofline")
    s = tr.summarize_planes(planes())
    run = SimpleNamespace(trace=s, peaks={"hbm_bytes_per_s": 819e9},
                          facts={"ranks": 4, "payload_bytes": 1 << 20})
    # 5 MiB over 819 GB/s = 6.4 us least, 20 us per run measured
    assert reader.read(run) == pytest.approx(
        100 * (5 * (1 << 20) / 819e9) / 20e-6)
    s.busiest.modules = {"jit_some_new_fold(1)": [2, 40e-6]}
    with pytest.raises(RuntimeError, match="ran none of"):
        reader.read(run)    # a renamed kernel must not drop out unseen
    run.facts["payload_bytes"] = None
    with pytest.raises(RuntimeError, match="one size"):
        reader.read(run)
