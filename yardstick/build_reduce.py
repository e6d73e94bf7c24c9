"""What the six set-up readers under `layer_metrics/` (`build_trace_s`,
`build_lower_s`, `build_compile_s`, `build_cache_misses`, `step_build_s`,
`kernel_traces`) share: the program's pvar family `build`
(`tpu_mpi/perfvars.py` `build_snapshot`, docs/observability.md "Set-up
spans") as the snapshot at the window's begin holds it, which is all of
set-up, and the rows that say, for a person, what is behind each number.

The family: `trace` / `lower` / `compile` = {"n", "s"}, the events and
seconds of JAX's own `jaxpr_trace_duration`, `jaxpr_to_mlir_module_duration`
and `backend_compile_duration` as the program's one `jax.monitoring`
listener heard them (a read from the persistent cache lies inside
`compile`), SUMMED OVER THREADS: four rank threads that compile at once add
up, so in an OSU cell a phase's seconds can pass the wall time it took
(`arming_s` beside them is a union of brackets and cannot); `cache` =
{"hits", "misses", "load_s", "saved_s"}; `by_fun` the three pairs by
function name as JAX gives it (`<lambda>` and `wrapped` are every program
of that name: read the count beside the seconds); `step` the names the program's step builders gave
their jitted functions; `kernels` the `pallas_call`s built under a trace, by
name. The listener is registered where the program first touches JAX
(`transformer_train_step`, `spmd_run`): what the benchmark built before that
(an OSU cell's operands and reference) is not in it. A program without the
family (the parent of the PR that added it) or with an empty one gives every
reader nothing.

With span sampling on (the traced run of a train cell: `step_build_s` asks
for it through `span_reduce.prepare`, as `arming_s` does in the OSU cells)
every event is a span of the `setup:` trace too, and `setup_spans_row`
prints them: the only run that publishes a train cell's `build.*` spans and
the Pallas import thread's `kernels.import`."""

from __future__ import annotations

from typing import Optional

PHASES = ("trace", "lower", "compile")
KEY = "build"


def family(run, at: str = "begin") -> Optional[dict]:
    return run.counters.get(at, {}).get(KEY) or None


def phase_seconds(run, phase: str) -> Optional[float]:
    """The family's seconds of one phase, and the row of the ten functions
    that took most of them."""
    fam = family(run)
    if fam is None:
        return None
    rows = sorted(((row[phase]["s"], name, row[phase]["n"])
                   for name, row in fam["by_fun"].items() if row[phase]["n"]),
                  reverse=True)
    run.row(f"build {phase}: {fam[phase]['n']} outermost, "
            f"{fam[phase]['s']:.3f} s summed over threads; of {len(rows)} "
            "names the heaviest (count, seconds; a nested trace is in its "
            "callers' too): "
            + "  ".join(f"{name} x{n} {s:.3f}" for s, name, n in rows[:10]))
    return float(fam[phase]["s"])


def built_in_window(run) -> list:
    """[(function, phase, events, seconds)] of what the window's end
    snapshot holds beyond its begin: what `compiles_in_window` counts and
    cannot name."""
    begin, end = family(run), family(run, "end")
    if end is None:
        return []
    was = (begin or {}).get("by_fun", {})
    out = []
    for name, row in end["by_fun"].items():
        for phase in PHASES:
            before = was.get(name, {}).get(phase, {"n": 0, "s": 0.0})
            if row[phase]["n"] > before["n"]:
                out.append((name, phase, row[phase]["n"] - before["n"],
                            row[phase]["s"] - before["s"]))
    return out


def setup_spans(fam: dict) -> list:
    """The `setup:` spans of set-up alone, from the buffer of a run that had
    span sampling on. The buffer holds what came after the window too (a
    traced train run compiles its step again, once a scope reducer), and the
    family `fam` of the window's begin says where set-up ends without a
    second clock: a build event is counted into `by_fun` and published as a
    span by the same call, so of each phase the first `n` spans in the order
    they were published are set-up's, `n` the events `by_fun` holds. Other
    spans (`kernels.import`, the arming ones) are set-up's where they began
    before the last of those ended. (Buffer and family start empty together
    in a run: `span_reduce.prepare` empties the one before the program has
    registered the listener of the other.)"""
    from tpu_mpi import tracectx
    left = {"build." + p: sum(row[p]["n"] for row in fam["by_fun"].values())
            for p in PHASES}
    built, others = [], []
    for s in tracectx.drain():
        if not str(s.get("trace", "")).startswith("setup:"):
            continue
        if s["name"] not in left:
            others.append(s)
        elif left[s["name"]] > 0:
            left[s["name"]] -= 1
            built.append(s)
    if not built:
        return []
    cut = max(s["t1"] for s in built)
    return built + [s for s in others if s["t0"] <= cut]


def setup_spans_row(run, fam: dict) -> None:
    """For a person, from `setup_spans`: count and seconds by name (summed
    over threads), `build.compile` cut by what the persistent cache did, how
    many of them lie under a set-up span of the program, and whether the
    first trace of a function in `fam["step"]` began before the Pallas
    import's thread was done (it then waited on the import lock). A program
    that publishes none prints nothing."""
    spans = setup_spans(fam)
    if not spans:
        return
    by_name, nested = {}, 0
    for s in spans:
        name = s["name"]
        if name == "build.compile":
            name += f"[{s.get('cache')}]"
        rec = by_name.setdefault(name, [0, 0.0])
        rec[0] += 1
        rec[1] += s["t1"] - s["t0"]
        nested += s["name"].startswith("build.") and s["parent"] is not None
    text = ("set-up spans, count and seconds summed over threads: "
            + "  ".join(f"{n} x{c} {t:.3f}"
                        for n, (c, t) in sorted(by_name.items()))
            + f"; build spans under a set-up span of the program: {nested}")
    imports = [s for s in spans if s["name"] == "kernels.import"]
    traces = [s for s in spans
              if s["name"] == "build.trace" and s.get("fun") in fam["step"]]
    if imports and traces:
        first = min(traces, key=lambda s: s["t0"])
        late = max(s["t1"] for s in imports) - first["t0"]
        text += (f"; kernels.import ended {abs(late):.3f} s "
                 f"{'after' if late > 0 else 'before'} the first trace of "
                 f"{first['fun']} began")
    run.row(text)
