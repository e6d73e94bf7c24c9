"""From a profiler trace (`*.xplane.pb`) to the numbers the per-layer
readers and the `device`/`breakdown` blocks need. Reads the file with
`jax.profiler.ProfileData` and nothing else.

What a trace of this repo on a v5e looks like (looked at by hand in PR 22,
see PERF.md "Reading a trace"):

- one plane per chip, named `/device:TPU:<n>`; its line `XLA Ops` holds one
  event per executed HLO op (name = the whole HLO instruction), its line
  `XLA Modules` one event per executable run (name = `jit_<function>(<fingerprint>)`,
  e.g. `jit_plain_fold(...)`, `jit_local_step(...)`); `Async XLA Ops` holds
  the copy-start..copy-done spans that overlap compute and is not counted
  as busy time; `#Chip0 ...` and `Megascale` planes are empty here;
- `/host:CPU` holds one unnamed line per host thread; the yardstick's own
  `jax.profiler.TraceAnnotation`s appear there under their names (`ys:op`,
  `ys:sync`, `ys:rebind`, `ys:barrier`, `ys:step`, `ys:readback`), and
  `ys:traced` brackets the profiled interval;
- all planes share one clock (nanoseconds from the start of the trace).

On the four-chip host the copies between chips leave no device event at all
(chips that only copy have no plane), an op's event includes its wait for
operands still arriving from another chip, and about half a second into the
star's traffic chip 0 reports `Trace Buffers Dropped` for the rest of the
interval; the reduction cuts the interval at the first drop and says so.

Busy time of a chip is the union of its op intervals, clipped to the
profiled interval; idle share is 1 - busy / interval. A gap is named by
the `ys:` annotation that covers most of it on any host thread."""

from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Iterable, Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
DROPPED = "Trace Buffers Dropped"     # on a chip's `XLA TraceMe` line
MARK_PREFIX = "ys:"
WINDOW_MARK = "ys:traced"
#: a gap shorter than this is the device's own hand-over between ops, not
#: something the host could close; it still counts as idle time
MIN_NAMED_GAP_NS = 2_000.0
TOP = 10

Interval = tuple  # (start_ns, end_ns)


def find_xplane(trace_dir: str) -> Optional[str]:
    """The newest `*.xplane.pb` the profiler wrote under `trace_dir`."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def union(intervals: Iterable[Interval]) -> list:
    """Sorted, disjoint union of (start, end) intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals: Iterable[Interval]) -> float:
    return float(sum(e - s for s, e in intervals))


def gaps(busy: list, lo: float, hi: float) -> list:
    """Complement of a disjoint sorted union inside [lo, hi]."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


@dataclass
class Chip:
    ordinal: int
    busy_s: float
    idle_share: float
    ops: dict = field(default_factory=dict)       # name -> [count, seconds]
    modules: dict = field(default_factory=dict)   # name -> [count, seconds]


@dataclass
class TraceSummary:
    window_s: float                 # the profiled interval that was kept
    dropped_s: float                # cut off its end: trace buffers dropped
    chips: list                     # [Chip], by ordinal
    marks: dict                     # ys: name -> [count, seconds], all threads
    idle_gaps: list                 # [[name, seconds]], busiest chip, top 10
    device_ops: list                # [[name, seconds]], busiest chip, top 10

    @property
    def busiest(self) -> Chip:
        return max(self.chips, key=lambda c: c.busy_s)

    def busy_mean_s(self, nchips: int) -> float:
        """Device busy seconds averaged over the `nchips` the cell used; a
        chip that left no op in the trace was busy for none of it."""
        return sum(c.busy_s for c in self.chips) / max(nchips, len(self.chips))

    def module_seconds(self, *functions: str, chip: Optional[Chip] = None):
        """(runs, seconds) of the executables jitted from one of the named
        `functions` (an `XLA Modules` event is `jit_<function>(<id>)`), on
        one chip (default: the busiest). The whole name has to match."""
        chip = chip or self.busiest
        runs, secs = 0, 0.0
        for name, (n, s) in chip.modules.items():
            if name.split("(", 1)[0] in {"jit_" + f for f in functions}:
                runs, secs = runs + n, secs + s
        return runs, secs


def short_name(name: str) -> str:
    """An `XLA Ops` event is named by its whole HLO instruction
    (`%fusion.151 = bf16[...] fusion(...)`); keep the instruction's name."""
    return name.split(" = ", 1)[0].lstrip("%")


def _events(line) -> list:
    return [(short_name(e.name), float(e.start_ns),
             float(e.start_ns + e.duration_ns)) for e in line.events]


def _tally(events: list, lo: float, hi: float) -> dict:
    out: dict = {}
    for name, s, e in events:
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        rec = out.setdefault(name, [0, 0.0])
        rec[0] += 1
        rec[1] += (e - s) / 1e9
    return out


def _top(tally: dict) -> list:
    rows = sorted(((n, v[1]) for n, v in tally.items()),
                  key=lambda r: -r[1])[:TOP]
    return [[n, s] for n, s in rows]


def _name_gaps(gap_list: list, marks: list) -> dict:
    """Seconds of idle gap by the host annotation that covers most of each
    gap. `marks` = [(name, start, end)] from every host thread."""
    marks = sorted(marks, key=lambda m: m[1])
    lengths = sorted(m[2] - m[1] for m in marks)
    # the few longest annotations (a barrier that waits out a whole block)
    # are tried against every gap; the rest are found by bisection
    cut = lengths[int(len(lengths) * 0.99)] if lengths else 0.0
    short = [m for m in marks if m[2] - m[1] <= cut]
    long_ = [m for m in marks if m[2] - m[1] > cut]
    starts = [m[1] for m in short]
    out: dict = {}
    for s, e in gap_list:
        name = "(device hand-over)"
        if e - s >= MIN_NAMED_GAP_NS:
            name, best, best_len = "(no ys: annotation)", 0.0, 0.0
            i = bisect.bisect_left(starts, s - cut)
            j = bisect.bisect_left(starts, e)
            for n, ms, me in short[i:j] + long_:
                ov = min(e, me) - max(s, ms)
                # most overlap wins; of two that cover the gap alike, the
                # shorter annotation says more about what the host did
                if ov > 0 and (ov > best
                               or (ov == best and me - ms < best_len)):
                    name, best, best_len = n, ov, me - ms
        out[name] = out.get(name, 0.0) + (e - s) / 1e9
    return out


def summarize_planes(planes: list) -> TraceSummary:
    """`planes` = [(plane name, [(line name, [(event, start, end)])])]: the
    trace as plain lists, so the arithmetic is testable without a file."""
    marks: list = []
    window: Optional[Interval] = None
    for pname, lines in planes:
        if pname != HOST_PLANE:
            continue
        for _lname, evs in lines:
            for name, s, e in evs:
                if name == WINDOW_MARK:
                    window = (s, e)
                elif name.startswith(MARK_PREFIX):
                    marks.append((name, s, e))
    devs, drops = [], []
    for pname, lines in planes:
        m = DEVICE_PLANE.match(pname)
        if not m:
            continue
        by_line = {ln: evs for ln, evs in lines}
        drops += [s for evs in by_line.values() for n, s, _e in evs
                  if n == DROPPED]
        if by_line.get(OPS_LINE):
            devs.append((int(m.group(1)), by_line[OPS_LINE],
                         by_line.get(MODULES_LINE, [])))
    if not devs:
        raise ValueError("the trace holds no device plane with an "
                         f"{OPS_LINE!r} line: no operation ran on a chip")
    if window is None:          # no bracket: first to last device event
        window = (min(e[1] for _, ops, _m in devs for e in ops),
                  max(e[2] for _, ops, _m in devs for e in ops))
    lo, hi = window
    # a chip whose trace buffers filled recorded nothing from there on:
    # what follows the first drop is no evidence of idleness, so it is cut
    kept = min([hi] + [d for d in drops if d > lo])
    dropped, hi = hi - kept, kept
    span = hi - lo
    chips, busy_of = [], {}
    for ordinal, ops, mods in sorted(devs, key=lambda d: d[0]):
        busy = clip(union((s, e) for _n, s, e in ops), lo, hi)
        busy_of[ordinal] = busy
        chips.append(Chip(ordinal, total(busy) / 1e9,
                          1.0 - total(busy) / span,
                          _tally(ops, lo, hi), _tally(mods, lo, hi)))
    out = TraceSummary(span / 1e9, dropped / 1e9, chips,
                       _tally(marks, lo, hi), [], [])
    top = out.busiest
    out.device_ops = _top(top.ops)
    named = _name_gaps(gaps(busy_of[top.ordinal], lo, hi),
                       clip_marks(marks, lo, hi))
    out.idle_gaps = sorted(([n, s] for n, s in named.items()),
                           key=lambda r: -r[1])[:TOP]
    return out


def clip_marks(marks: list, lo: float, hi: float) -> list:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in marks
            if min(e, hi) > max(s, lo)]


def read_planes(path: str) -> list:
    """The planes of an `.xplane.pb` (or a gzip of one) as plain lists."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    return [(pl.name, [(ln.name, _events(ln)) for ln in pl.lines])
            for pl in pd.planes]


def summarize(path: str) -> TraceSummary:
    return summarize_planes(read_planes(path))


def describe(path: str, rows: int = 12) -> str:
    """Planes, lines and the heaviest events of a trace, for the look by
    hand that comes before any code is written against it."""
    text = []
    for pname, lines in read_planes(path):
        text.append(f"PLANE {pname}")
        for lname, evs in lines:
            tally = _tally(evs, float("-inf"), float("inf"))
            text.append(f"  LINE {lname!r}: {len(evs)} events, "
                        f"{len(tally)} names")
            for n, s in _top(tally)[:rows]:
                text.append(f"    {s * 1e3:12.3f} ms  x{tally[n][0]:<7d} {n[:110]}")
    return "\n".join(text)


if __name__ == "__main__":
    import sys
    print(describe(sys.argv[1]))
