"""`ready_reduce`'s arithmetic on hand-made spans and device planes: the
median from "launch returned" to "output ready", the tiling of a rank's
period and its coverage, `below` / `above` and the window they leave, a
contradicted stamp, a program without the stamps, and the five entries of
the manifest by name. Nothing here is a timing."""

import os
import types

import pytest

from yardstick import harness, ready_reduce as rr, span_reduce as sr
from yardstick.tests.test_span_reduce import (FakeRun, US, load, op_tree,
                                              profile_of, span)

CELL = "osu-allreduce-4r1c.small-reuse"
READERS = {"fold_ready_us": "fold kernels",
           "between_ops_us": "host path as a whole",
           "idle_launch_us": "device", "idle_outside_us": "device",
           "device_clock_window_us": "device"}
NEED_A_STAMP = ("fold_ready_us", "between_ops_us", "device_clock_window_us")
PERIOD = 1000 * US
WAITER, LAST = 217 * US, 87 * US        # op_tree's brackets, by its defaults


def sampled(rounds_kept, t0=10.0, stamp=True, jump=None, slow=0.0):
    """The trees of the rounds the program sampled, a period apart (`jump` =
    (round, seconds): everything from that round on comes that much later,
    as after another rung's block; `slow`: what a sampled op takes longer
    than another, in the caller's time after it). With `stamp` every op but
    a thread's first carries `t_prev`, its previous op's end, sampled or
    not."""
    spans = []
    for nth, rnd in enumerate(rounds_kept):
        late = nth * slow + (jump[1] if jump and rnd >= jump[0] else 0.0)
        for rank in range(4):
            last = rank == rnd % 4
            tree = op_tree(rnd, rank, t0 + rnd * PERIOD + late + rank * US,
                           last=last)
            if stamp and rnd:
                before = LAST if rank == (rnd - 1) % 4 else WAITER
                first_of_block = jump and rnd == jump[0]
                after_sampled = slow if rnd - 1 in rounds_kept else 0.0
                tree[0]["t_prev"] = tree[0]["t0"] - PERIOD + before \
                    - after_sampled - (50 * US if first_of_block else 0.0)
            spans += tree
    return spans


def dispatch_of(spans, rnd):
    return next(s for s in spans
                if s["name"] == "fold_dispatch" and s["round"] == rnd)


def stamp_folds(spans, summary_of, rounds_kept, launch_us, device_us=10.0,
                way_back_us=40.0, skip=()):
    """`fold.done` under each round's last arriver and the fold's device
    event: the device starts `launch_us` after the dispatch returned, works
    for `device_us`, and the watcher sees it `way_back_us` later. Returns
    the device events, in profiler ns, once `summary_of` is aligned."""
    events = []
    for rnd, launch in zip(rounds_kept, launch_us):
        fold = dispatch_of(spans, rnd)
        start = fold["t1"] + launch * US
        end = start + device_us * US
        events.append((start, end))
        if rnd not in skip:
            spans.append(span(f"w{rnd}", f"c0r{rnd}k{rnd % 4}", "fold.done",
                              fold["t0"], end + way_back_us * US,
                              cid=0, round=rnd, rank=rnd % 4))
    summary = summary_of(spans)
    summary.fold_starts = sorted(
        (s * 1e9 + summary.offset_ns, e * 1e9 + summary.offset_ns)
        for s, e in events)
    return summary


def aligned(spans):
    return sr.align(spans, profile_of(spans, 9.0, 12.0))


def test_fold_ready_is_the_median_from_launch_returned_to_output_ready():
    kept = [0, 8, 16, 24, 32]
    spans = sampled(kept)
    launches = [300.0, 320.0, 340.0, 900.0, 310.0]
    summary = stamp_folds(spans, aligned, kept, launches, skip=(16,))
    run = FakeRun(summary)
    # round 16's output was donated away: four rounds have both stamps
    want = sorted(x + 10.0 + 40.0 for x in (300.0, 320.0, 900.0, 310.0))
    assert load("fold_ready_us").read(run) \
        == pytest.approx((want[1] + want[2]) / 2)
    (row,) = run.rows
    assert "of 5 sampled rounds that dispatched a fold 1 have no fold.done" \
        in row
    assert "median over 4 of those rounds, 10.0 us" in row
    assert f"watcher, {(want[1] + want[2]) / 2 - 10.0:.1f} us" in row


def profiled(run, ops, wall_s, busy_s):
    """The harness's side of the profiled interval: `ops` made in `wall_s`,
    the busiest chip busy for `busy_s` of them (`host_overhead_us`' own)."""
    run.trace = types.SimpleNamespace(
        window_s=wall_s, dropped_s=0.0,
        busiest=types.SimpleNamespace(busy_s=busy_s))
    run.traced_ops = lambda: ops
    return run


def test_the_tiling_is_set_beside_the_intervals_wall_time_an_op():
    kept = [0, 3, 8, 17, 24, 30, 41]
    spans = sampled(kept, jump=(24, 9000 * US))     # the 1 GiB rung's block
    # 42 ops a rank in the interval, one of them the large rung's 8 ms fold
    run = profiled(FakeRun(aligned(spans)), 42, 42 * PERIOD + 8000 * US,
                   8000 * US)
    ops = sr.sampled_ops(run)
    assert len(ops) == 4 * len(kept)
    # every op but a thread's first has a `t_prev`; the first after the other
    # rung's block waited 50 us more (its previous op was the barrier)
    n = 4 * (len(kept) - 1)
    bracket = (3 * WAITER + LAST) / 4
    between = (n * (PERIOD - bracket) + 4 * 50 * US) / n
    assert load("between_ops_us").read(run) == pytest.approx(between / US)
    (row,) = run.rows
    wall = (42 * PERIOD + 8000 * US) / 42
    assert "over ALL its ops" in row and f"{wall / US:.1f} us" in row
    assert "(`host_overhead_us`) 1000.0 us" in row
    cover = 100.0 * (between + bracket) / PERIOD
    assert f"the tiling reads {cover:.2f}% of that" in row
    assert 100.0 < cover < 101.0
    # a rehearsal takes no profile: the mean all the same, nothing beside it
    alone = FakeRun(aligned(sampled([5, 13])))
    assert load("between_ops_us").read(alone) == pytest.approx(
        (PERIOD - bracket) / US)
    assert "tiling reads" not in alone.rows[0]


def test_a_sampled_op_that_is_slower_shows_as_a_tiling_over_100():
    """A sampled op takes 60 us longer (its caller waits that much longer
    for the result) and sometimes follows another: the interval's wall time
    an op is mostly the seven in eight's."""
    kept = [0, 8, 9, 21, 29, 34, 42]
    run = profiled(FakeRun(aligned(sampled(kept, slow=60 * US))), 43,
                   43 * PERIOD + 7 * 60 * US, 0.0)
    bracket = (3 * WAITER + LAST) / 4
    # round 9 follows a sampled round: its four callers waited 60 us longer
    between = PERIOD - bracket + 4 * 60 * US / (4 * 6)
    assert load("between_ops_us").read(run) == pytest.approx(between / US)
    cover = 100.0 * (between + bracket) / (PERIOD + 7 * 60 * US / 43)
    assert f"the tiling reads {cover:.2f}%" in run.rows[0]


def fit_of(summary, least_s=lambda r: 0.0):
    rounds = [r for r in rr.folds_dispatched(summary.ops)
              if "fold.done" in r["spans"]]
    return rr.shift_window(summary, rounds, least_s)


def test_the_window_is_what_the_two_stamps_leave_the_device_plane():
    kept = [0, 8, 16, 24]
    spans = sampled(kept)
    summary = stamp_folds(spans, aligned, kept, [300.0, 250.0, 420.0, 280.0],
                          way_back_us=40.0)
    fit = fit_of(summary)
    # below = the dispatch's 50 us + the launch, at least 300; above = the
    # way back, 40: the plane could slide 300 us earlier and 40 us later
    assert fit["lo"] == pytest.approx(-300 * US)
    assert fit["hi"] == pytest.approx(40 * US)
    assert (fit["satisfied"], fit["contradicted"]) == (4, [])
    assert [r["op"]["round"] for r, _s, _e in fit["matches"]] == kept
    run = FakeRun(summary)
    run.peaks = None
    assert load("device_clock_window_us").read(run) == pytest.approx(340.0)
    (row,) = run.rows
    assert "contradicts a stamp of 0; moved by -300.0 to +40.0 us" in row
    assert "contradicts none of 4" in row
    # the fold's device time beside the host-clock reading, from those rounds
    assert load("fold_ready_us").read(run) == pytest.approx(
        (280.0 + 300.0) / 2 + 10.0 + 40.0)
    assert "median over 4 of those rounds, 10.0 us" in run.rows[-1]


EARLY = 1450 * US
LAUNCHES = {8: 300.0, 16: 250.0, 24: 420.0}
BIG = lambda r: 5e-3 if r["op"]["nbytes"] == 1 << 30 else 0.0


def misplaced(with_big):
    """Three sampled rounds of 32, each round's fold on the device plane,
    which the profiler put `EARLY` (1.45 ms, more than a period) early; with
    the other rung's round, 50 periods on: 7 ms of fold for its 1 GiB."""
    kept = sorted(LAUNCHES)
    spans = sampled(kept)
    every = sampled(range(32), stamp=False)     # the folds of ALL the rounds
    events = []
    for rnd in range(32):
        start = dispatch_of(every, rnd)["t1"] + LAUNCHES.get(rnd, 300.0) * US
        events.append((start, start + 10 * US))
    stamp_folds(spans, aligned, kept, [LAUNCHES[r] for r in kept])
    if with_big:
        big = op_tree(7, 0, 10.0 + 50 * PERIOD, True, nbytes=1 << 30, cid=1)
        launch = next(sp for sp in big if sp["name"] == "fold_dispatch")
        events.append((launch["t1"] + 300 * US, launch["t1"] + 7300 * US))
        big.append(span("wbig", "c1r7k0", "fold.done", launch["t0"],
                        events[-1][1] + 40 * US, cid=1, round=7, rank=0))
        spans = spans + big
    summary = aligned(spans)
    to_ns = lambda t: (t - EARLY) * 1e9 + summary.offset_ns
    summary.fold_starts = [(to_ns(s), to_ns(e)) for s, e in events]
    summary.gaps = [(to_ns(a[1]), to_ns(b[0]))
                    for a, b in zip(events, events[1:])]
    return summary


def test_a_misplaced_plane_is_found_and_by_how_much():
    """The first fold to start after a round's dispatch began is a LATER
    round's. The window says where the plane belongs; the large rung's round
    tells that place from those a period away."""
    fit = fit_of(misplaced(False))
    # small rounds alone: where the plane belongs, or whole periods from there
    assert fit["satisfied"] == 3 and len(fit["contradicted"]) == 3
    periods_off = (fit["lo"] - (EARLY - 300 * US)) / PERIOD
    assert periods_off == pytest.approx(round(periods_off), abs=0.2)
    # the large round, known by the least time its bytes take, anchors it
    both = misplaced(True)
    fit = fit_of(both, BIG)
    assert fit["satisfied"] == 4
    # the way back is 40 us, the least launch 250 + the dispatch's 50
    assert fit["lo"] == pytest.approx(EARLY - 300 * US)
    assert fit["hi"] == pytest.approx(EARLY + 40 * US)
    assert sorted(r["op"]["round"] for r, _s, _e in fit["matches"]) \
        == [7, 8, 16, 24]
    run = FakeRun(both)
    run.facts["ranks"], run.peaks = 4, {"hbm_bytes_per_s": 819e9}
    assert load("device_clock_window_us").read(run) == pytest.approx(340.0)
    assert "contradicts a stamp of 4 (cid, round: 0, 8;  0, 16;  0, 24;  " \
        "1, 7; ...); moved by +1150.0 to +1490.0 us" in run.rows[0]
    assert "contradicts none of 4" in run.rows[0]


def test_the_idle_cut_is_made_with_the_plane_moved_into_its_window():
    """As placed, the first fold to start after a sampled round's dispatch
    began is another round's, and the cut names that one's gap. Moved into
    the window each round's gap is its own: at the window's late end a
    launch reads the way back's 40 us too long, at its middle 130 us too
    short, and the two ends are what the stamps can tell."""
    summary = misplaced(True)
    run = FakeRun(summary)
    run.facts["ranks"], run.peaks = 4, {"hbm_bytes_per_s": 819e9}
    mean = (sum(LAUNCHES.values()) + 300.0) / 4     # the large round's too
    assert load("idle_launch_us").read(run) == pytest.approx(mean - 130.0)
    placed = rr._cut_per_round(summary)
    assert placed["launch"] / US != pytest.approx(mean, abs=100.0)
    late = rr._cut_per_round(summary, EARLY + 40 * US)
    assert late["launch"] / US == pytest.approx(mean + 40.0)
    # what launch loses, the time before the round's op began gains
    mid = rr._cut_per_round(summary, EARLY - 130 * US)
    assert load("idle_outside_us").read(run) == pytest.approx(
        mid["outside the program"] / US)
    assert (mid["outside the program"] - late["outside the program"]) / US \
        == pytest.approx(170.0)
    (row,) = run.rows
    assert "(by +1150.0, to its middle, by +1490.0 us)" in row
    assert f"/ {mean - 130.0:.1f} / {mean + 40.0:.1f};" in row
    assert f"as the profiler placed it: launch {placed['launch'] / US:.1f}" \
        in row


def test_a_program_without_the_stamps_reads_nothing_for_the_three():
    kept = [0, 8, 16]
    spans = sampled(kept, stamp=False)
    summary = aligned(spans)
    to_ns = lambda t: t * 1e9 + summary.offset_ns
    folds = [(to_ns(dispatch_of(spans, rnd)["t1"] + 300 * US),
              to_ns(dispatch_of(spans, rnd)["t1"] + 310 * US))
             for rnd in kept]
    summary.fold_starts = folds
    summary.gaps = [(to_ns(summary.lo_s), folds[0][0])] + [
        (a[1], b[0]) for a, b in zip(folds, folds[1:])]
    run = FakeRun(summary)
    run.peaks = None
    for reader in NEED_A_STAMP:
        assert load(reader).read(run) is None, reader
    assert run.rows == []
    # the two cuts are the accepted reader's, per sampled round
    named, lags = sr.attribute_gaps(summary)
    assert len(lags) == 3 and named["launch"] == pytest.approx(3 * 300 * US)
    assert load("idle_launch_us").read(run) == pytest.approx(300.0)
    assert load("idle_outside_us").read(run) == pytest.approx(
        named["outside the program"] / 3 / US)
    load("idle_attributed_share").read(run)
    assert "launch 0.000900 (300.0)" in run.rows[-1]
    # and nothing at all where the clocks cannot be aligned
    silent = FakeRun(None)
    for reader in READERS:
        assert load(reader).read(silent) is None, reader


MANIFEST = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))


@pytest.mark.parametrize("name", sorted(READERS))
def test_the_entry_by_name(name):
    (spec,) = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    assert spec == {"name": name, "unit": "us", "better": "lower",
                    "source": "program_span", "layer": READERS[name],
                    "moves": "coll_latency_p50", "workloads": [CELL]}
    mod = load(name)
    assert mod.prepare is sr.prepare and callable(mod.read)
    assert READERS[name] in {m["layer"] for m in MANIFEST["per_layer"]
                             if m["name"] not in READERS}


def test_the_small_cell_reports_the_five_and_what_it_reported():
    cell = harness.Cell(MANIFEST, CELL)
    names = [m["name"] for m in cell.per_layer]
    assert set(READERS) <= set(names) and len(set(names)) == len(names)
    assert {"host_overhead_us", "idle_attributed_share", "coll_latency_tail",
            "fold_dispatch_us", "op_span_coverage"} <= set(names)
    assert len(MANIFEST["per_layer"]) <= 128
