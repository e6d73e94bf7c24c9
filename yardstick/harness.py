"""What every cell shares: the manifest (`BENCHMARK.json`) and the files it
names, the window (set-up clock, compile count, counter snapshots, the
profiled interval) and the one JSON line a run ends with.

Nothing here knows a configuration, a traffic mix or a per-layer metric by
name: each is found from `BENCHMARK.json` by its name, in a file of its own
(`configs/<config>.json`, `traffic/<traffic>.json`, `generators/<kind>.py`,
`layer_metrics/<metric>.py`), so a later PR adds cells as new files."""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import time
from typing import Any, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_DIR = os.path.join(HERE, ".trace")      # listed in .gitignore
NOT_MEASURED = "not measured"


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import one of the yardstick's by-name files (a generator, a
    reference, a per-layer reader)."""
    if name in sys.modules:
        return sys.modules[name]
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{name}: no file {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of `workloads`, with the files and metrics it names."""

    def __init__(self, manifest: dict, name: str, rehearse: bool = False):
        cells = {w["name"]: w for w in manifest["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                           f"{sorted(cells)}")
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        conf = {c["name"]: c for c in manifest["configs"]}[self.entry["config"]]
        self.config = load_json(os.path.join(ROOT, conf["file"]))
        self.traffic = load_json(os.path.join(
            HERE, "traffic", self.entry["traffic"] + ".json"))
        if rehearse:                # tiny sizes, stated in the files themselves
            self.config = {**self.config, **self.config.get("rehearse", {})}
            self.traffic = {**self.traffic, **self.traffic.get("rehearse", {})}
        self.end_to_end = [m for m in manifest["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in manifest["per_layer"]
                          if name in m.get("workloads", [name])]

    def generator(self):
        kind = self.config["kind"]
        return load_module(os.path.join(HERE, "generators", kind + ".py"),
                           "ys_generator_" + kind)

    def reference(self):
        kind = self.config["kind"]
        return load_module(os.path.join(HERE, "reference", kind + ".py"),
                           "ys_reference_" + kind)

    def readers(self) -> list:
        """A metric `<reader>` or `<reader>.<tag>` is read by
        `layer_metrics/<reader>.py`: a per-layer metric names the one
        end-to-end metric it moves, so the same reading carries a tag where
        it stands beside another end-to-end metric."""
        out = []
        for m in self.per_layer:
            reader = m["name"].split(".", 1)[0]
            out.append((m, load_module(
                os.path.join(HERE, "layer_metrics", reader + ".py"),
                "ys_layer_" + reader)))
        return out


def program_counters() -> dict:
    """The program's own counters, read whole (`perfvars.snapshot()`, which
    carries `overlap.plans.stats()` as `plan_cache`); the readers take
    what they need from the window's two snapshots."""
    from tpu_mpi import perfvars
    return perfvars.snapshot()


class Run:
    """The state of one run that generators write and readers read."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 rehearse: bool, t_process: float):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace_on, self.rehearse = trace, rehearse
        self.config, self.traffic = cell.config, cell.traffic
        self.chips = cell.chips
        self.peaks: Optional[dict] = None       # None in a CPU rehearsal
        self.devices: list = []
        self.t_process = t_process          # set-up's origin (see run.py)
        self.backend_s = 0.0                # JAX's client start-up, left out
        self.setup_s: Optional[float] = None
        self.window_s: Optional[float] = None
        self.compiles = 0                       # backend compiles so far
        self.compiles_in_window: Optional[int] = None
        self.counters: dict = {}                # "begin" / "end" snapshots
        self.results: dict = {}                 # the generator's metrics
        self.facts: dict = {}                   # ops, payload, ... for readers
        self.prepared: dict = {}                # what readers' prepare() left
        self.trace: Any = None                  # trace_reduce.TraceSummary
        self.traced: dict = {}                  # ops / wall_s of the interval
        self.phases: list = []                  # (set-up phase, age in s)
        self.memory_held = 0                    # most seen at the window's ends
        self._trace_state = "idle"
        self._t_window = 0.0
        self._compiles_at_begin = 0
        self._mark: Any = None
        # a traced run profiles this steady stretch inside the window
        self.trace_after_s = min(2.0, 0.2 * seconds)
        self.trace_len_s = min(2.5, 0.3 * seconds)

    def phase(self, name: str) -> None:
        """A set-up phase has ended: its name and the process's age, for
        the row that says where set-up time went."""
        self.phases.append((name, time.perf_counter() - self.t_process))

    def memory_row(self, label: str) -> None:
        """What the runtime says of the fullest chip's memory, for a person:
        `memory_peak_bytes` in the result line is `peak_bytes_in_use`."""
        stats = max((d.memory_stats() or {} for d in self.devices),
                    key=lambda m: m.get("peak_bytes_in_use", 0))
        self.row(f"device memory {label}: " + "  ".join(
            f"{k} {v}" for k, v in sorted(stats.items())))

    def row(self, text: str) -> None:
        """A line of samples for a person to read. A CPU rehearsal prints
        none: its timings are no measurement."""
        if not self.rehearse:
            print(text)

    # -- the window ---------------------------------------------------------
    def window_begin(self) -> None:
        """Set-up is over: everything from process start to here, less the
        backend's own start-up, is `setup_s`. Called by one thread, with
        every other one parked."""
        self.counters["begin"] = program_counters()
        self.memory_held = max(self.memory_held, memory_now(self))
        self._compiles_at_begin = self.compiles
        self._t_window = time.perf_counter()
        self.setup_s = self._t_window - self.t_process

    def elapsed(self) -> float:
        return time.perf_counter() - self._t_window

    def window_end(self, ops_done: int) -> None:
        self.window_s = self.elapsed()
        self.compiles_in_window = self.compiles - self._compiles_at_begin
        self.counters["end"] = program_counters()
        self.memory_held = max(self.memory_held, memory_now(self))
        if self._trace_state == "on":           # the window ended under it
            self._stop_trace(ops_done)

    # -- the profiled interval ----------------------------------------------
    def trace_tick(self, ops_done: int) -> None:
        """Called by one thread between blocks, every other one parked or
        about to park in a barrier: starts the profiler once the window is
        `trace_after_s` old and stops it `trace_len_s` later."""
        if not self.trace_on or self.rehearse:
            return
        now = self.elapsed()
        if self._trace_state == "idle" and now >= self.trace_after_s:
            self._start_trace(ops_done)
        elif self._trace_state == "on" and \
                time.perf_counter() - self.traced["t0"] >= self.trace_len_s:
            self._stop_trace(ops_done)

    def _start_trace(self, ops_done: int) -> None:
        import shutil
        import jax
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # only TraceAnnotations, no frames
        opts.host_tracer_level = 2
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        self._mark = jax.profiler.TraceAnnotation("ys:traced")
        self._mark.__enter__()
        self.traced = {"t0": time.perf_counter(), "ops0": ops_done}
        self._trace_state = "on"

    def _stop_trace(self, ops_done: int) -> None:
        import jax
        self.traced["wall_s"] = time.perf_counter() - self.traced["t0"]
        self.traced["ops"] = ops_done - self.traced["ops0"]
        self._mark.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self._trace_state = "done"

    def traced_ops(self) -> float:
        """Ops (steps) that the kept part of the profiled interval covers:
        the harness's own count between the interval's two block
        boundaries, scaled down where the trace's end was cut."""
        if self.trace is None or not self.traced.get("ops"):
            return 0.0
        whole = self.trace.window_s + self.trace.dropped_s
        return self.traced["ops"] * self.trace.window_s / whole

    def reduce_trace(self) -> None:
        if self._trace_state != "done":
            return
        from yardstick.trace_reduce import find_xplane, summarize
        path = find_xplane(TRACE_DIR)
        if path is None:
            raise RuntimeError(f"the profiler left no xplane under {TRACE_DIR}")
        self.traced["path"] = path
        self.trace = summarize(path)


def annotate(name: str):
    """A host span in the profiler's own trace (a no-op costing about a
    microsecond while no profile is being taken)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def count_compiles(run: Run) -> None:
    """Count every backend compile request from now on (a hit in the
    persistent cache is one too: it is host work no window should hold)."""
    import jax.monitoring

    def on_event(event: str, _secs: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            run.compiles += 1
    jax.monitoring.register_event_duration_secs_listener(on_event)


def memory_now(run: Run) -> int:
    """Bytes held on the fullest chip right now: live arrays plus what the
    runtime has reserved for programs' temporaries. On the v5e the
    runtime's `peak_bytes_in_use` follows live arrays only; a jitted
    program's temporaries are in `bytes_reserved` (checked against XLA's
    own memory analysis in PR 22), which stays reserved between runs."""
    held = 0
    for d in run.devices:
        m = d.memory_stats() or {}
        held = max(held, int(m.get("bytes_in_use", 0))
                   + int(m.get("bytes_reserved", 0)))
    return held


def device_block(run: Run) -> dict:
    d0 = run.devices[0]
    peak = max([run.memory_held, memory_now(run)] + [
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in run.devices])
    out = {"platform": d0.platform, "kind": d0.device_kind,
           "count": len(run.devices), "memory_peak_bytes": peak}
    if not run.rehearse:
        out["backend_start_s"] = run.backend_s  # what setup_s leaves out
    if run.trace is not None:
        out["busy_s"] = run.trace.busy_mean_s(len(run.devices))
        out["window_s"] = run.trace.window_s
    return out


def result_line(run: Run, values: dict, specs: list) -> str:
    """The contract's last line. `values` = {metric: number or None}."""
    metrics = {}
    for m in specs:
        v = values.get(m["name"])
        if v is None:
            continue                        # a reader that found nothing
        metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    line = {"correct": bool(run.results.get("correct", False)),
            "attempted": int(run.results.get("attempted", 0)),
            "failed": int(run.results.get("failed", 0)),
            "metrics": metrics, "device": device_block(run)}
    if run.trace is not None:
        line["breakdown"] = {"device_ops": run.trace.device_ops,
                             "idle_gaps": run.trace.idle_gaps}
    return json.dumps(line)
