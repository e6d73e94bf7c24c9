"""The 8 B op's millisecond, tiled on the host's clock, and the device
plane's place on that clock held from both sides: what five readers under
`layer_metrics/` take from two stamps the program gained in PR 51, beside
the spans `span_reduce` already reads (which this file imports and leaves
as it is).

`fold.done` (tpu_mpi/perfvars.py `watch`): the watcher thread's host-clock
stamp of the moment a registered fold's output was ready, on one chip as on
four, a child of the round's last arriver's `op`. With the last arriver's
`fold_dispatch` it brackets everything between "the launch returned" and
"the host knows the device is done" -> `fold_ready_us`. And it bounds the
fold's device event from ABOVE as the dispatch's begin bounds it from
below -> `device_clock_window_us`: the window of shifts of the device plane
that contradict no stamp (`shift_window`): a check on the yardstick, which
no change to the program can move. The cut of the chip's idle gaps into
`launch` and `outside the program` (`span_reduce.attribute_gaps`, which the
accepted reader only prints) places a device event among host spans: it is
made with the plane moved to that window's middle, and at its two ends ->
`idle_launch_us`, `idle_outside_us`.

`t_prev` on an `op` span: when the thread's previous host-path op ended.
`op.t0 - t_prev` is what the caller did between two ops (here: waited for
its result, and the loop) -> `between_ops_us`. With the `op` bracket it
tiles the op's period, on `time.monotonic()` alone; the reader sets the two
beside the profiled interval's wall time an op, `host_overhead_us`' own.

A program without a stamp (the parent of the PR that added them) leaves the
three readers that need one with nothing to read: they report nothing, and
the two cuts are made on the plane as the profiler placed it."""

from __future__ import annotations

import bisect
import dataclasses
import statistics
from typing import Optional

from yardstick import span_reduce as sr, stats

#: a fold's device event is looked for this far from its round's two stamps
MAX_SHIFT_S = 5e-3
GAPS_KEY = "ready_reduce.gaps"
FIT_KEY = "ready_reduce.fit"


def _us(seconds: float) -> float:
    return seconds * 1e6


def folds_dispatched(ops: list) -> list:
    """The records of the round's last arrivers: who dispatched a fold."""
    return [r for r in ops
            if r["op"].get("last") and "fold_dispatch" in r["spans"]]


# -- the device plane's place on the host's clock ------------------------------

def shift_window(summary: sr.Summary, rounds: list,
                 least_s=lambda record: 0.0) -> Optional[dict]:
    """By how much the device plane may be moved on the host's clock so that
    it contradicts no stamp. Each of `rounds` (last arrivers' records that
    have `fold.done`) brackets its fold's device event on the host's clock:
    the device cannot have started the fold before the host began to
    dispatch it (`fold_dispatch`.t0) nor can the watcher have seen its
    output ready before the device was done (`fold.done`.t1). So a fold
    event [s, e] can be that round's only if the plane is moved later by a
    shift within [t0 - s, t1 - e], and only if it lasts the `least_s` the
    round's bytes take at the chip's peak (an 8 B fold's event cannot be a
    1 GiB round's: the large rounds anchor a plane that the small ones,
    a period apart, would let slip by whole periods). A round is satisfied
    by the union of those intervals over the events near it, and the window
    is where the most rounds are (all of them, where the stamps and the
    plane can agree at all); of several such the widest. No shift at all
    inside the window: the plane sits where the profiler put it, and could
    slide earlier by `-lo` (the smallest device start after a dispatch
    began) and later by `hi` (the smallest `fold.done` after a device end).

    Returns {lo, hi (seconds, later is positive), satisfied, contradicted
    (the records of the rounds a stamp of which the plane contradicts where
    it is), matches [(record, device start, device end)] under the window's
    middle shift, on the monotonic clock as the profiler placed them}, or
    None where the plane has no fold."""
    folds = [((s - summary.offset_ns) / 1e9, (e - summary.offset_ns) / 1e9)
             for s, e in summary.fold_starts]
    starts = [s for s, _e in folds]
    marks, contradicted = [], []
    for r in rounds:
        began = r["spans"]["fold_dispatch"]["t0"]
        done = r["spans"]["fold.done"]["t1"]
        near = folds[bisect.bisect_left(starts, began - MAX_SHIFT_S):
                     bisect.bisect_right(starts, done + MAX_SHIFT_S)]
        own: list = []              # this round's intervals, merged, ascending
        least = least_s(r)
        for s, e in reversed(near):
            lo, hi = began - s, done - e
            if hi < lo or e - s < least:
                continue
            if own and lo <= own[-1][1]:
                own[-1][1] = max(own[-1][1], hi)
            else:
                own.append([lo, hi])
        if not any(lo <= 0.0 <= hi for lo, hi in own):
            contradicted.append(r)
        for lo, hi in own:
            marks += [(lo, 0), (hi, 1)]
    if not marks:
        return None
    marks.sort()
    best, count, opened, windows = 0, 0, None, []
    for at, closing in marks:
        if closing:
            if count == best and opened is not None:
                windows.append((opened, at))
                opened = None
            count -= 1
        else:
            count += 1
            if count > best:
                best, windows = count, []
            if count == best:
                opened = at
    lo, hi = max(windows, key=lambda w: (w[1] - w[0], -abs(w[0] + w[1])))
    shift = (lo + hi) / 2
    matches = []
    for r in rounds:
        began = r["spans"]["fold_dispatch"]["t0"]
        done = r["spans"]["fold.done"]["t1"]
        least = least_s(r)
        for s, e in folds[bisect.bisect_left(starts, began - shift):]:
            if e + shift > done:
                break
            if e - s >= least:
                matches.append((r, s, e))
                break
    return {"lo": lo, "hi": hi, "satisfied": best,
            "contradicted": contradicted, "matches": matches}


def clock_fit(run) -> Optional[dict]:
    """`shift_window` over every sampled round of the profiled interval that
    has its `fold.done`, of any rung; once a run."""
    if FIT_KEY not in run.prepared:
        summary = sr.summarize(run)
        rounds = [] if summary is None else [
            r for r in folds_dispatched(summary.ops)
            if "fold.done" in r["spans"]]
        ranks, peaks = run.facts.get("ranks"), getattr(run, "peaks", None)
        rate = peaks["hbm_bytes_per_s"] if ranks and peaks else None
        least = (lambda r: stats.fold_bytes(
            ranks, r["op"].get("nbytes") or 0) / rate) if rate \
            else (lambda r: 0.0)
        run.prepared[FIT_KEY] = shift_window(summary, rounds, least) \
            if rounds else None
        if run.prepared[FIT_KEY] is not None:
            run.prepared[FIT_KEY]["rounds"] = len(rounds)
    return run.prepared[FIT_KEY]


def device_clock_window_us(run) -> Optional[float]:
    """The width of the window of shifts under which the device plane
    contradicts no stamp: a check on the yardstick (it says how far the
    profiler's placement of the plane can be trusted), not a target of the
    program's. The row says where the window lies (all of it to one side of
    no shift: the profiler misplaced the plane by that much at least) and
    what the stamps then say of the fold's two halves."""
    fit = clock_fit(run)
    if fit is None:
        return None
    lo, hi, n = fit["lo"], fit["hi"], fit["rounds"]
    wrong = fit["contradicted"]
    text = (f"device clock window over {n} sampled rounds: as the profiler "
            f"placed it the device plane contradicts a stamp of {len(wrong)}"
            + (" (cid, round: " + "  ".join(
                f"{r['op'].get('cid')}, {r['op'].get('round')};"
                for r in wrong[:8]) + " ...)" if wrong else "")
            + f"; moved by {_us(lo):+.1f} to {_us(hi):+.1f} us (+ is later) "
            f"it contradicts none of {fit['satisfied']}")
    if fit["matches"]:
        began = statistics.median(s - r["spans"]["fold_dispatch"]["t0"]
                                  for r, s, _e in fit["matches"])
        ready = statistics.median(r["spans"]["fold.done"]["t1"] - e
                                  for r, _s, e in fit["matches"])
        text += (f"; a round's fold being the one so found, the device "
                 f"starts it {_us(began + lo):.1f} to {_us(began + hi):.1f} "
                 f"us after its dispatch began and the watcher sees it "
                 f"ready {_us(ready - hi):.1f} to {_us(ready - lo):.1f} us "
                 f"after the device's end (medians, at the window's two "
                 f"ends)")
    run.row(text)
    return _us(hi - lo)


# -- fold_ready_us -------------------------------------------------------------

def fold_ready_us(run) -> Optional[float]:
    """Median over the sampled rounds of the per-op rung of (`fold.done`.t1
    - `fold_dispatch`.t1), both the last arriver's, host clock alone."""
    ops = sr.sampled_ops(run)
    if ops is None:
        return None
    dispatched = folds_dispatched(ops)
    stamped = [r for r in dispatched if "fold.done" in r["spans"]]
    if not stamped:
        return None
    ready = statistics.median(
        r["spans"]["fold.done"]["t1"] - r["spans"]["fold_dispatch"]["t1"]
        for r in stamped)
    text = (f"fold ready: of {len(dispatched)} sampled rounds that "
            f"dispatched a fold {len(dispatched) - len(stamped)} have no "
            f"fold.done (the output was donated away before the watcher "
            f"came); the launch returned -> the watcher saw the output "
            f"ready, median {_us(ready):.1f} us")
    mine = {id(r) for r in stamped}
    found = [e - s for r, s, e in (clock_fit(run) or {"matches": []})["matches"]
             if id(r) in mine]
    if found:
        device = statistics.median(found)
        text += (f"; the fold on the device (XLA Modules "
                 f"{' / '.join(sr.FOLD_MODULES)}), median over "
                 f"{len(found)} of those rounds, {_us(device):.1f} us; what "
                 f"is left, the launch and the completion's way to the "
                 f"watcher, {_us(ready - device):.1f} us")
    run.row(text)
    return _us(ready)


# -- between_ops_us ------------------------------------------------------------

def between_ops_us(run) -> Optional[float]:
    """Mean of (`op`.t0 - `t_prev`) over the sampled ops of the per-op rung,
    all ranks: the caller's own time between two ops."""
    ops = sr.sampled_ops(run)
    if ops is None:
        return None
    mine = [r for r in ops if r["op"].get("t_prev") is not None]
    if not mine:
        return None
    between = sum(r["t0"] - r["op"]["t_prev"] for r in mine) / len(mine)
    bracket = sum(r["t1"] - r["t0"] for r in ops) / len(ops)
    text = (f"between two ops: the caller's own time (op.t0 - t_prev), mean "
            f"over {len(mine)} sampled ops, {_us(between):.1f} us; with the "
            f"op bracket, {_us(bracket):.1f} us, {_us(between + bracket):.1f}: "
            f"a sampled op's period from its thread's previous op's end to "
            f"its own, tiled")
    made = run.traced_ops() if run.trace is not None else 0
    if made:
        # `host_overhead_us`' own arithmetic, over the same interval
        wall = run.trace.window_s / made
        overhead = wall - run.trace.busiest.busy_s / made
        text += (f"; the profiled interval's wall time an op, over ALL its "
                 f"ops, sampled or not and the large rung's too (the "
                 f"harness's count), {_us(wall):.1f} us, and less the "
                 f"busiest chip's busy time (`host_overhead_us`) "
                 f"{_us(overhead):.1f} us: the tiling reads "
                 f"{100.0 * (between + bracket) / overhead:.2f}% of that "
                 f"(over 100: what a sampled op takes longer than one that "
                 f"is not)")
    run.row(text)
    return _us(between)


# -- idle_launch_us, idle_outside_us -------------------------------------------

def _cut_per_round(summary: sr.Summary, later_s: float = 0.0
                   ) -> Optional[dict]:
    """`span_reduce.attribute_gaps`' seconds by name, per sampled round
    whose gaps were cut, with the device plane moved `later_s` on the host's
    clock (the cut reads the plane only through `offset_ns`)."""
    cut = sr.attribute_gaps(dataclasses.replace(
        summary, offset_ns=summary.offset_ns - 1e9 * later_s))
    if cut is None:
        return None
    named, lags = cut
    return {name: seconds / len(lags) for name, seconds in named.items()}


def idle_cuts(run) -> Optional[dict]:
    """The cut of the chip's idle gaps at a sampled round's host spans, per
    round, once a run. The cut takes a round's fold to be the first to start
    after its dispatch began, which holds only where the device plane lies
    on the host's clock where the program's stamps allow it: so the plane
    is moved to the middle of `clock_fit`'s window first, and the row gives
    the cut at the window's two ends too, which is how far the stamps can
    tell `launch` from `outside the program`. On a program without
    `fold.done` there is no window: the cut is the accepted reader's, on
    the plane as the profiler placed it."""
    if GAPS_KEY in run.prepared:
        return run.prepared[GAPS_KEY]
    run.prepared[GAPS_KEY] = None
    summary = sr.summarize(run)
    if summary is None:
        return None
    fit = clock_fit(run)
    if fit is None:
        run.prepared[GAPS_KEY] = _cut_per_round(summary)
        return run.prepared[GAPS_KEY]
    lo, hi = fit["lo"], fit["hi"]
    at = [_cut_per_round(summary, s) for s in (lo, (lo + hi) / 2, hi)]
    placed = _cut_per_round(summary)
    run.prepared[GAPS_KEY] = at[1]
    if all(at):
        text = (f"idle gaps cut with the device plane moved into its window "
                f"(by {_us(lo):+.1f}, to its middle, by {_us(hi):+.1f} us), "
                f"us per sampled round: "
                + "  ".join(
                    f"{name} {_us(at[0][name]):.1f} / {_us(at[1][name]):.1f}"
                    f" / {_us(at[2][name]):.1f};" for name in sr.GAP_NAMES)
                + " the stamps tell launch from outside no nearer than "
                  "that")
        if placed:
            text += (f"; as the profiler placed it: launch "
                     f"{_us(placed['launch']):.1f}, outside the program "
                     f"{_us(placed['outside the program']):.1f}")
        run.row(text)
    return run.prepared[GAPS_KEY]


def idle_cut_us(run, name: str) -> Optional[float]:
    cut = idle_cuts(run)
    return None if cut is None else _us(cut[name])
