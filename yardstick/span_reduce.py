"""From the program's own op spans (`tpu_mpi.tracectx`'s buffer) and the
profiler trace of the same interval to the numbers the span readers under
`layer_metrics/` need. One drain per run, kept on `run.prepared`.

What the program gives (tpu_mpi/perfvars.py, docs/observability.md "Op
spans"), while `trace_sample > 0`: per host-path collective and rank one
`op` span (attributes `coll`, `cid`, `round`, `rank`, `nbytes`, `lane`,
`last`) with children `front_door`, `lock`, `rendezvous` (cut into
`rdv_skew`, `rdv_fold`, `rdv_wake`), `fold_dispatch` (child `colocate`),
`copyout`; from the watcher thread `copy_in.done`, `fold.done`,
`copy_out.done`; all on `time.monotonic()`. From the channel's door to its
end each such op also holds a `jax.profiler.TraceAnnotation(
"tpu_mpi:<coll>")` whose stats carry `cid`, `round`, `rank` and `mono_ns`,
the monotonic reading taken just before it was opened.

One clock. The offset between the profiler's clock and the monotonic one is
the median of (annotation start - its `mono_ns`). It is checked against the
spans themselves: the program reads the clock before it opens an op's
annotation (`mono_ns`) and again once it is open (the `op` span's `t_ann`);
every annotation of the interval, moved by the offset, must have begun
between its op's two readings, give or take `MAX_RESIDUAL_NS`, or nothing
is read from the spans at all (`summarize` returns None and every reader
with it). The annotation's distance from `mono_ns` alone is printed too: a
few microseconds, except where a rank thread lost its core between the two
readings.

`prepare` is the hook `run.py` calls in the traced run only, before any rank
thread exists: it turns span sampling on, for one round in `1 / SAMPLE`. All
ranks keep the same rounds. Every round (`TPU_MPI_TRACE_SAMPLE=1`) slowed
the 8 B op by 7% and moved the readings that stand beside these (the tail by
14%); one round in 8 is under 1% (PERF.md section 6, PR 23), and is the rate
at which the watcher costs the four-chip cell nothing. A program without op
spans (the parent of the PR that added them) leaves the buffer empty, and
every reader here reports nothing."""

from __future__ import annotations

import bisect
import os
import statistics
from dataclasses import dataclass, field
from typing import Optional

from yardstick import trace_reduce as tr

ANNOTATION_PREFIX = "tpu_mpi:"
MAX_RESIDUAL_NS = 20_000.0
#: the children of `op` that tile it (`rendezvous` is its three parts)
PARTS = ("front_door", "lock", "rdv_skew", "rdv_fold", "rdv_wake",
         "fold_dispatch", "copyout")
#: what an idle gap before a fold is cut into, in order
GAP_NAMES = ("outside the program", "front_door", "lock", "fold_dispatch",
             "launch")
#: the executables that fold (yardstick/layer_metrics/fold_roofline.py)
FOLD_MODULES = ("jit_plain_fold", "jit_chain")
#: a fold's module event starts this close to the end of the gap before it
GAP_END_TOL_NS = 5_000.0
KEY = "span_reduce"
#: the share of a channel's rounds whose ops publish their spans
SAMPLE = 0.125


def prepare(run) -> None:
    """Span sampling on, the buffer empty: before the operands exist."""
    if run.prepared.get("spans_on"):
        return
    os.environ["TPU_MPI_TRACE_SAMPLE"] = str(SAMPLE)
    from tpu_mpi import config, tracectx
    config.load(refresh=True)
    tracectx.reset()
    run.prepared["spans_on"] = True


# -- the profiler's side ------------------------------------------------------

@dataclass
class Profile:
    """What the span readers take from one `.xplane.pb`, in profiler ns."""
    window: Optional[tuple] = None          # `ys:traced`
    annotations: list = field(default_factory=list)   # (start, mono_ns, key)
    chips: dict = field(default_factory=dict)   # ordinal -> (ops, modules)


def read_profile(path: str) -> Profile:
    from jax.profiler import ProfileData
    out = Profile()
    for plane in ProfileData.from_file(path).planes:
        chip = tr.DEVICE_PLANE.match(plane.name)
        if plane.name == tr.HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name == tr.WINDOW_MARK:
                        out.window = (float(e.start_ns),
                                      float(e.start_ns + e.duration_ns))
                    elif e.name.startswith(ANNOTATION_PREFIX):
                        st = dict(e.stats)
                        if "mono_ns" in st:
                            out.annotations.append((
                                float(e.start_ns), float(st["mono_ns"]),
                                (str(st.get("cid")), int(st.get("round", -1)),
                                 int(st.get("rank", -1)))))
        elif chip:
            ops, mods = [], []
            for line in plane.lines:
                evs = [(tr.short_name(e.name), float(e.start_ns),
                        float(e.start_ns + e.duration_ns))
                       for e in line.events]
                if line.name == tr.OPS_LINE:
                    ops = evs
                elif line.name == tr.MODULES_LINE:
                    mods = evs
            if ops:
                out.chips[int(chip.group(1))] = (ops, mods)
    return out


def fit_offset(annotations: list) -> Optional[float]:
    """profiler ns = monotonic ns + offset."""
    if not annotations:
        return None
    return statistics.median(s - m for s, m, _k in annotations)


# -- the spans' side ----------------------------------------------------------

def group_ops(spans: list) -> list:
    """`op` spans with their descendants' seconds by name, and the
    boundaries the gap attribution needs. `spans` = tracectx's dicts."""
    ops, kids = {}, {}
    for s in spans:
        if s.get("name") == "op":
            ops[s["span"]] = s
    root_of = {}
    for s in spans:
        if s.get("name") != "op":
            root_of[s["span"]] = s.get("parent")
    for s in spans:
        if s.get("name") == "op":
            continue
        top = s.get("parent")
        while top in root_of:           # a grandchild: climb to the op
            top = root_of[top]
        if top in ops:
            kids.setdefault(top, []).append(s)
    out = []
    for sid, op in ops.items():
        rec = {"op": op, "t0": op["t0"], "t1": op["t1"], "parts": {},
               "spans": {}}
        for k in kids.get(sid, ()):
            rec["parts"][k["name"]] = rec["parts"].get(k["name"], 0.0) \
                + (k["t1"] - k["t0"])
            rec["spans"].setdefault(k["name"], k)
        out.append(rec)
    out.sort(key=lambda r: r["t0"])
    return out


@dataclass
class Summary:
    ops: list               # group_ops records inside the profiled interval
    ops_whole: list         # ... its end included where the device trace's
                            # was cut (trace buffers dropped)
    offset_ns: float
    residual_median_ns: float   # |annotation - its mono_ns|
    residual_worst_ns: float
    outside_worst_ns: float     # annotation outside [mono_ns, op.t_ann]
    lo_s: float             # the interval on the monotonic clock
    hi_s: float
    gaps: list = field(default_factory=list)    # busiest chip, profiler ns
    fold_starts: list = field(default_factory=list)     # (start, end) ns
    dropped: int = 0

    def chosen(self, coll: Optional[str], nbytes: Optional[int],
               whole: bool = False) -> list:
        return [r for r in (self.ops_whole if whole else self.ops)
                if (coll is None or r["op"].get("coll") == coll)
                and (nbytes is None or r["op"].get("nbytes") == nbytes)]


def align(spans: list, profile: Profile, kept_s: Optional[float] = None
          ) -> Optional[Summary]:
    """Spans and profile on one clock, or None where that cannot be shown:
    no annotation, no op span inside the interval, or an annotation that
    began more than `MAX_RESIDUAL_NS` outside its op's two clock reads."""
    offset = fit_offset(profile.annotations)
    if offset is None or profile.window is None:
        return None
    lo, hi = profile.window
    whole_s = (hi - offset) / 1e9
    if kept_s is not None:              # trace buffers dropped: cut the end
        hi = min(hi, lo + kept_s * 1e9)
    lo_s, hi_s = (lo - offset) / 1e9, (hi - offset) / 1e9
    whole = [r for r in group_ops(spans)
             if r["t0"] >= lo_s and r["t1"] <= whole_s]
    inside = [r for r in whole if r["t1"] <= hi_s]
    if not inside:
        return None
    starts = {k: (s, m) for s, m, k in profile.annotations}
    residuals, outside = [], []
    for r in inside:
        op = r["op"]
        key = (str(op.get("cid")), int(op.get("round", -1)),
               int(op.get("rank", -1)))
        if key in starts and "t_ann" in op:
            began = starts[key][0] - offset         # monotonic ns
            first = starts[key][1]                  # read before it opened
            second = op["t_ann"] * 1e9              # ... and after
            if not r["t0"] * 1e9 <= first <= second <= r["t1"] * 1e9:
                return None         # not this op's: another clock altogether
            residuals.append(abs(began - first))
            outside.append(max(0.0, first - began, began - second))
    if not outside or max(outside) > MAX_RESIDUAL_NS:
        return None
    return Summary(inside, whole, offset, statistics.median(residuals),
                   max(residuals), max(outside), lo_s, hi_s)


def device_gaps(summary: Summary, profile: Profile) -> None:
    """The busiest chip's idle gaps and its fold executables' starts,
    inside the interval the summary kept."""
    lo = summary.lo_s * 1e9 + summary.offset_ns
    hi = summary.hi_s * 1e9 + summary.offset_ns
    best = None
    for _ordinal, (ops, mods) in sorted(profile.chips.items()):
        busy = tr.clip(tr.union((s, e) for _n, s, e in ops), lo, hi)
        if best is None or tr.total(busy) > best[0]:
            best = (tr.total(busy), busy, mods)
    if best is None:
        return
    _busy_s, busy, mods = best
    summary.gaps = tr.gaps(busy, lo, hi)
    summary.fold_starts = sorted(
        (s, e) for n, s, e in mods if n.split("(", 1)[0] in FOLD_MODULES)


#: idle seconds before the fold of a round whose ops published no spans
UNSAMPLED = "(a fold of a round not sampled)"
NOT_A_FOLD = "(not a fold's)"


def attribute_gaps(summary: Summary) -> Optional[tuple]:
    """Idle seconds by name. A gap that ends where a fold's device event
    starts belongs to the last arriver of that fold's round; where that
    round is one of the sampled, the gap is cut at that op's own
    boundaries, else it stays `UNSAMPLED`. The fold is a sampled round's if
    it is the first to start after that round's dispatch began: the rounds
    between two sampled ones dispatch folds of their own. Any other gap
    stays `NOT_A_FOLD`. Returns (seconds by name, for each sampled round
    whose gaps were cut the seconds from its dispatch's begin to the device's
    start), or None where none was cut. (A round has more than one gap: the
    fold's own ops leave nanoseconds between them. The lag is the check on
    the device plane's clock against the host's: it cannot be negative.)"""
    if not summary.gaps:
        return None
    last = [r for r in summary.ops
            if r["op"].get("last") and "fold_dispatch" in r["spans"]]
    dispatch_ns = [r["spans"]["fold_dispatch"]["t0"] * 1e9
                   + summary.offset_ns for r in last]
    starts = [s for s, _e in summary.fold_starts]
    out = {n: 0.0 for n in GAP_NAMES}
    out[UNSAMPLED] = out[NOT_A_FOLD] = 0.0
    lag: dict = {}
    for gs, ge in summary.gaps:
        i = bisect.bisect_right(starts, ge + GAP_END_TOL_NS) - 1
        if i < 0 or starts[i] < gs - GAP_END_TOL_NS \
                or summary.fold_starts[i][1] <= ge:
            out[NOT_A_FOLD] += (ge - gs) / 1e9
            continue
        j = bisect.bisect_right(dispatch_ns, ge) - 1
        if j < 0 or (i > 0 and starts[i - 1] >= dispatch_ns[j]):
            out[UNSAMPLED] += (ge - gs) / 1e9
            continue
        lag.setdefault(j, (starts[i] - dispatch_ns[j]) / 1e9)
        r = last[j]
        to_ns = lambda t: t * 1e9 + summary.offset_ns
        fold = r["spans"]["fold_dispatch"]
        door = r["spans"].get("front_door")
        cuts = [float("-inf"), to_ns(r["t0"]),
                to_ns(door["t1"] if door else r["t0"]),
                to_ns(fold["t0"]), to_ns(fold["t1"]), float("inf")]
        for name, a, b in zip(GAP_NAMES, cuts, cuts[1:]):
            ov = min(ge, b) - max(gs, a)
            if ov > 0:
                out[name] += ov / 1e9
    return (out, list(lag.values())) if lag else None


# -- one summary per run ------------------------------------------------------

def summarize(run) -> Optional[Summary]:
    """The run's op spans inside its profiled interval, aligned; None
    where the program has no op spans, no profile was taken (a rehearsal)
    or the clocks cannot be shown to agree."""
    if KEY in run.prepared:
        return run.prepared[KEY]
    run.prepared[KEY] = None
    if run.trace is None or not run.traced.get("path"):
        return None
    try:
        from tpu_mpi import tracectx
    except ImportError:
        return None
    profile = read_profile(run.traced["path"])
    offset = fit_offset(profile.annotations)
    if offset is None or profile.window is None:
        return None
    lo_s = (profile.window[0] - offset) / 1e9 - 1.0
    hi_s = (profile.window[1] - offset) / 1e9 + 1.0
    try:
        spans = tracectx.drain(t0=lo_s, t1=hi_s)
    except TypeError:                   # a program older than the op spans
        spans = tracectx.drain()
    summary = align(spans, profile, kept_s=run.trace.window_s)
    if summary is None:
        run.row("op spans: none inside the profiled interval, or their "
                "clock cannot be aligned with the profiler's; the span "
                "readers report nothing")
        return None
    summary.dropped = int(getattr(tracectx, "dropped", lambda: 0)())
    device_gaps(summary, profile)
    run.prepared[KEY] = summary
    run.row(f"op spans: {len(summary.ops)} ops inside the profiled interval; "
            f"profiler clock = monotonic + {summary.offset_ns / 1e9:.6f} s "
            f"(from {len(profile.annotations)} tpu_mpi: annotations); an "
            f"annotation's start against its op span's: median "
            f"{summary.residual_median_ns / 1e3:.2f} us, worst "
            f"{summary.residual_worst_ns / 1e3:.2f} us, and outside the "
            f"op's two clock reads by at most "
            f"{summary.outside_worst_ns / 1e3:.2f} us; spans the buffer "
            f"refused: {summary.dropped}")
    return summary


def op_rung_bytes(run) -> Optional[int]:
    """Payload bytes of the ops the cell's end-to-end metric samples: the
    rung synced per op where there is one (`coll_latency_p50`), else the
    one size of the traffic."""
    import jax.numpy as jnp
    tr_ = run.traffic
    counts = tr_.get("counts") or []
    sync = tr_.get("sync")
    sync = sync if isinstance(sync, list) else [sync] * len(counts)
    per_op = [c for c, s in zip(counts, sync) if s == "per-op"]
    pick = per_op or counts
    if len(pick) != 1:
        return None
    return int(pick[0]) * jnp.dtype(tr_.get("dtype", "float32")).itemsize


def sampled_ops(run) -> Optional[list]:
    """The op records that the span readers average over, or None: the
    sampled ops of the whole profiled interval. Host spans need no device
    event beside them, so they count also where the device's trace buffers
    gave out early (the four-chip cell keeps half a second of 2.5: one
    sampled round)."""
    summary = summarize(run)
    if summary is None:
        return None
    ops = summary.chosen(run.facts.get("op"), op_rung_bytes(run), whole=True)
    return ops or None


def part_us(run, *names: str) -> Optional[float]:
    """Total seconds of the named child spans over the sampled ops, all
    ranks, per `op` span, in microseconds."""
    ops = sampled_ops(run)
    if ops is None:
        return None
    total = sum(r["parts"].get(n, 0.0) for r in ops for n in names)
    return total / len(ops) * 1e6


def parts_row(run) -> Optional[dict]:
    """Mean microseconds per op of every part, of what they leave open and
    of the `op` bracket itself; printed once, for a person."""
    ops = sampled_ops(run)
    if ops is None:
        return None
    n = len(ops)
    row = {p: sum(r["parts"].get(p, 0.0) for r in ops) / n * 1e6
           for p in PARTS}
    row["op"] = sum(r["t1"] - r["t0"] for r in ops) / n * 1e6
    row["(open)"] = row["op"] - sum(row[p] for p in PARTS)
    if not run.prepared.get("parts_row_shown"):
        run.prepared["parts_row_shown"] = True
        lanes = {}
        for r in ops:
            lanes[r["op"].get("lane")] = lanes.get(r["op"].get("lane"), 0) + 1
        run.row(f"op span parts, mean us per op over {n} op spans (lanes "
                f"{lanes}): " + "  ".join(f"{k} {v:.2f}"
                                          for k, v in row.items()))
    return row


def watched_rounds(run) -> Optional[list]:
    """The rounds of the profiled interval that the program's watcher
    stamped (those whose spans were sampled), oldest first: for each the
    last arriver's `copy_in.done` and `fold.done` and every rank's
    `copy_out.done`. Each of those spans begins at its dispatch on the host,
    which runs rounds ahead of the chips, so only the distance between two
    completions is time the device spent. Printed once: the medians a
    person wants beside the metric."""
    ops = sampled_ops(run)
    if ops is None:
        return None
    by_round: dict = {}
    for r in ops:
        key = (str(r["op"].get("cid")), r["op"].get("round"))
        rec = by_round.setdefault(key, {"outs": []})
        for name, slot in (("copy_in.done", "in"), ("fold.done", "fold")):
            if name in r["spans"]:
                rec[slot] = r["spans"][name]
        if "copy_out.done" in r["spans"]:
            rec["outs"].append(r["spans"]["copy_out.done"])
    rounds = sorted((rec for rec in by_round.values()
                     if "in" in rec and "fold" in rec and rec["outs"]),
                    key=lambda rec: rec["in"]["t0"])
    if not rounds:
        return None
    for r in rounds:
        r["home"] = max(o["t1"] for o in r["outs"])     # the last result
    if not run.prepared.get("watched_shown"):
        run.prepared["watched_shown"] = True
        ms = lambda xs: statistics.median(xs) * 1e3
        run.row(f"watcher, medians over {len(rounds)} stamped rounds, ms: "
                f"operands on the folding chip -> fold's output ready "
                f"{ms([r['fold']['t1'] - r['in']['t1'] for r in rounds]):.3f}; "
                f"output ready -> last result on its rank's chip "
                f"{ms([r['home'] - r['fold']['t1'] for r in rounds]):.3f}; "
                f"with the rounds the host runs ahead: dispatch -> operands "
                f"on the folding chip "
                f"{ms([r['in']['t1'] - r['in']['t0'] for r in rounds]):.3f}, "
                f"a rank's copy-out dispatch -> its result there, the slowest "
                f"{ms([max(o['t1'] - o['t0'] for o in r['outs']) for r in rounds]):.3f}")
    return rounds
