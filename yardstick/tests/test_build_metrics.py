"""The six set-up metrics of PR 34 (`build_trace_s`, `build_lower_s`,
`build_compile_s`, `build_cache_misses`, `step_build_s`, `kernel_traces`):
each reader on a fake run whose begin snapshot holds the program's `build`
family, lacks it (the parent's) or holds it empty; the rows they print; the
manifest's entries, asserted BY NAME AND BY CONTENT, their cells as a subset
and never by their place in `per_layer`, so that no PR's append falsifies
anything here; and a CPU rehearsal of a train cell and of an OSU cell."""

import json
import os
import types

import pytest

from tpu_mpi import config, perfvars, tracectx
from yardstick import build_reduce, harness, span_reduce
from test_generators import rehearse, run_py

FLAGSHIP = "flagship-d1024-1c.step-b8s1024"
OLMOE = "olmoe-1b-7b-1c.lm-step-b2s4096"
KEX = "k-exaone-236b-a23b-1c.lm-step-b1s8192"
PGU = "openpangu-ultra-moe-718b-1c.lm-step-b1s4096"
SMALL = "osu-allreduce-4r1c.small-reuse"
OSU = ["osu-allreduce-4r1c.large-reuse", SMALL, "osu-allreduce-4r4c.large-reuse"]
TRAIN = [FLAGSHIP, OLMOE, KEX, PGU]
RUNTIME, STEP = "launcher and runtime", "train step"
MINE = {
    "build_trace_s": ("s", "program_span", RUNTIME, OSU + TRAIN),
    "build_lower_s": ("s", "program_span", RUNTIME, OSU + TRAIN),
    "build_compile_s": ("s", "program_span", RUNTIME, OSU + TRAIN),
    "build_cache_misses": ("count", "program_counter", RUNTIME, OSU + TRAIN),
    "step_build_s": ("s", "program_span", STEP, TRAIN),
    "kernel_traces": ("count", "program_counter", STEP, TRAIN),
}
READERS = {name: harness.load_module(
    os.path.join(harness.HERE, "layer_metrics", name + ".py"),
    "ys_layer_" + name) for name in MINE}


def pairs(trace, lower, compile_):
    return {"trace": {"n": trace[0], "s": trace[1]},
            "lower": {"n": lower[0], "s": lower[1]},
            "compile": {"n": compile_[0], "s": compile_[1]}}


FAMILY = {
    **pairs((3, 2.5), (3, 1.25), (3, 4.0)),
    "cache": {"hits": 2, "misses": 1, "load_s": 0.5, "saved_s": 30.0},
    "by_fun": {"local_step": pairs((1, 2.0), (1, 1.0), (1, 3.5)),
               "forward": pairs((2, 0.75), (0, 0.0), (0, 0.0)),
               "<lambda>": pairs((2, 0.5), (2, 0.25), (2, 0.5))},
    "step": ["local_step"],
    "kernels": {"grouped_matmul_fwd": 8, "causal_attention_fwd": 2},
}


class Fake(types.SimpleNamespace):
    def row(self, text):
        self.rows.append(text)


def fake(begin, end=None):
    return Fake(counters={"begin": begin, "end": begin if end is None else end},
                rows=[], rehearse=False)


@pytest.fixture(autouse=True)
def no_spans_left_over(monkeypatch):
    """The span buffer is the process's: what an earlier traced rehearsal
    published is not this test's, nor this one's a later test's."""
    monkeypatch.delenv("TPU_MPI_TRACE_SAMPLE", raising=False)
    config.load(refresh=True)
    tracectx.reset()
    perfvars.reset()        # the family and the buffer start empty together
    yield
    monkeypatch.undo()
    config.load(refresh=True)
    tracectx.reset()


@pytest.fixture(scope="module")
def manifest():
    return harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))


# -- the readers ----------------------------------------------------------------

@pytest.mark.parametrize("name, want", [
    ("build_trace_s", 2.5), ("build_lower_s", 1.25), ("build_compile_s", 4.0),
    ("build_cache_misses", 1), ("step_build_s", 6.5), ("kernel_traces", 10)])
def test_each_reader_takes_its_number_from_the_begin_snapshot(name, want):
    run = fake({"build": FAMILY, "arming_s": 0.0})
    got = READERS[name].read(run)
    assert got == want and type(got) is type(want)
    assert len(run.rows) == 1               # the table behind the number


@pytest.mark.parametrize("name", sorted(MINE))
@pytest.mark.parametrize("begin", [
    {"gmm_lowerings": {"kernel": 6}},       # the parent: no such family
    {"build": {}},                          # pvars off, or nothing built
], ids=["absent", "empty"])
def test_a_program_without_the_family_gives_nothing(name, begin):
    run = fake(begin)
    assert READERS[name].read(run) is None and run.rows == []
    assert READERS[name].read(Fake(counters={}, rows=[])) is None


def test_the_steps_seconds_need_a_named_step_and_kernels_a_kernel():
    bare = {**FAMILY, "step": [], "kernels": {}}
    assert READERS["step_build_s"].read(fake({"build": bare})) is None
    assert READERS["kernel_traces"].read(fake({"build": bare})) is None
    # a step that was named and never built (its rows are not there)
    unbuilt = {**FAMILY, "step": ["other_step"]}
    assert READERS["step_build_s"].read(fake({"build": unbuilt})) is None
    assert READERS["build_trace_s"].read(fake({"build": bare})) == 2.5


def test_the_rows_name_the_heaviest_functions_and_the_kernels():
    run = fake({"build": FAMILY})
    READERS["build_trace_s"].read(run)
    assert "local_step x1 2.000  forward x2 0.750  <lambda> x2 0.500" \
        in run.rows[0]
    assert "3 outermost, 2.500 s" in run.rows[0]
    run = fake({"build": FAMILY})
    READERS["build_lower_s"].read(run)
    assert "forward" not in run.rows[0]     # never lowered on its own
    run = fake({"build": FAMILY})
    READERS["kernel_traces"].read(run)
    assert "grouped_matmul_fwd x8" in run.rows[0]
    run = fake({"build": FAMILY})
    READERS["step_build_s"].read(run)
    assert "local_step: trace x1 2.000 lower x1 1.000 compile x1 3.500" \
        in run.rows[0]
    run = fake({"build": FAMILY})
    READERS["build_cache_misses"].read(run)
    assert run.rows == ["persistent cache in set-up: hits 2  misses 1  reads "
                        "0.500 s for 30.000 s of compiles saved"]


def test_the_steps_reader_prints_the_setup_spans_it_asked_for():
    """`step_build_s` is the one reader of a train cell that turns span
    sampling on; what the traced run then publishes is printed by name, the
    compiles by what the cache did, and the Pallas import against the step's
    first trace."""
    assert READERS["step_build_s"].prepare is span_reduce.prepare
    for sid, (name, t0, t1, parent, extra) in enumerate([
            ("kernels.import", 0.5, 1.4, None, {}),
            ("fold.compile", 0.0, 9.0, None, {}),
            ("build.trace", 1.0, 3.0, None, {"fun": "local_step"}),
            ("build.trace", 1.5, 2.0, None, {"fun": "forward"}),
            ("build.lower", 3.0, 4.0, None, {"fun": "local_step"}),
            ("build.compile", 4.0, 4.5, None,
             {"fun": "local_step", "cache": "hit"}),
            ("build.compile", 5.0, 7.0, "1", {"fun": "fold", "cache": "miss"}),
            ("build.compile", 7.0, 7.25, "1", {"fun": "add", "cache": "off"}),
            # after the window (the family holds three compiles, not four):
            # a scope reducer builds the step again, past the cache
            ("kernels.import", 30.0, 30.5, None, {}),
            ("build.compile", 31.0, 43.0, None,
             {"fun": "local_step", "cache": "off"})]):
        tracectx.emit_setup_span(name, t0, t1, "rank ?", str(sid), parent,
                                 **extra)
    run = fake({"build": FAMILY})
    assert READERS["step_build_s"].read(run) == 6.5
    assert run.rows[1] == (
        "set-up spans, count and seconds summed over threads: "
        "build.compile[hit] x1 0.500  build.compile[miss] x1 2.000  "
        "build.compile[off] x1 0.250  build.lower x1 1.000  "
        "build.trace x2 2.500  fold.compile x1 9.000  "
        "kernels.import x1 0.900; build spans under a set-up span of the "
        "program: 2; kernels.import ended 0.400 s after the first trace of "
        "local_step began")
    # an import that was done in time, and a program that publishes nothing
    tracectx.reset()
    tracectx.emit_setup_span("kernels.import", 0.0, 0.75, "rank ?", "a")
    tracectx.emit_setup_span("build.trace", 1.0, 3.0, "rank ?", "b",
                             fun="local_step")
    run = fake({"build": FAMILY})
    build_reduce.setup_spans_row(run, FAMILY)
    assert run.rows[0].endswith("kernels.import ended 0.250 s before the "
                                "first trace of local_step began")
    tracectx.reset()
    build_reduce.setup_spans_row(run, FAMILY)
    assert len(run.rows) == 1


def test_what_was_built_inside_the_window_is_named():
    late = json.loads(json.dumps(FAMILY))
    late["by_fun"]["local_step"]["compile"] = {"n": 2, "s": 5.0}
    late["by_fun"]["mean"] = pairs((1, 0.25), (1, 0.125), (1, 0.5))
    run = fake({"build": FAMILY}, {"build": late})
    assert build_reduce.built_in_window(run) == [
        ("local_step", "compile", 1, 1.5), ("mean", "trace", 1, 0.25),
        ("mean", "lower", 1, 0.125), ("mean", "compile", 1, 0.5)]
    assert READERS["build_cache_misses"].read(run) == 1
    assert run.rows[1].startswith("built INSIDE the window")
    assert "local_step compile x1 1.500" in run.rows[1]
    assert build_reduce.built_in_window(fake({"build": FAMILY})) == []
    assert build_reduce.built_in_window(fake({})) == []


# -- the manifest ----------------------------------------------------------------

def test_the_six_entries_by_name_and_content(manifest):
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name, (unit, source, layer, cells) in MINE.items():
        spec = dict(by_name[name])
        assert set(cells) <= set(spec.pop("workloads")), name
        assert spec == {
            "name": name, "unit": unit, "better": "lower", "source": source,
            "layer": layer, "moves": "setup_s"}, name
    names = [m["name"] for m in manifest["per_layer"]]
    assert len(set(names)) == len(names)
    assert set(OSU + TRAIN) <= {w["name"] for w in manifest["workloads"]}
    setup = next(m for m in manifest["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup         # every cell reports it


def test_every_cell_reports_its_share_of_them(manifest):
    for cell in OSU + TRAIN:
        entry = harness.Cell(manifest, cell)
        names = {m["name"] for m in entry.per_layer}
        want = {n for n, (*_, cells) in MINE.items() if cell in cells}
        assert names & set(MINE) == want, cell
        assert len(want) == (6 if cell in TRAIN else 4)
        for _spec, mod in entry.readers():
            assert hasattr(mod, "read")
    # a train cell arms nothing: its one reader that asks for the spans
    assert [n for n in MINE if hasattr(READERS[n], "prepare")] \
        == ["step_build_s"]


# -- rehearsals ------------------------------------------------------------------

def test_a_train_cells_rehearsal_holds_the_family():
    run = rehearse(FLAGSHIP, trace=True)
    fam = run.counters["begin"]["build"]
    assert fam["step"] == ["local_step"]
    step = fam["by_fun"]["local_step"]
    assert [step[p]["n"] for p in build_reduce.PHASES] == [1, 1, 1]
    assert run.values["step_build_s"] == pytest.approx(
        sum(step[p]["s"] for p in build_reduce.PHASES))
    for phase in build_reduce.PHASES:
        assert run.values[f"build_{phase}_s"] == fam[phase]["s"] > 0
        # the benchmark's own programs are in the family beside the step's
        assert fam[phase]["n"] > 1
    assert run.values["build_cache_misses"] == 0    # no cache on the CPU
    assert run.values["kernel_traces"] is None      # nor a kernel
    assert build_reduce.built_in_window(run) == []
    assert run.compiles_in_window == 0
    # span sampling was on (`step_build_s.prepare`): every event a span too
    # (a rehearsal prints no row: the same row on a run that does)
    shown = fake({})
    build_reduce.setup_spans_row(shown, fam)
    (row,) = shown.rows
    events = sum(r["compile"]["n"] for r in fam["by_fun"].values())
    assert events == sum(int(part.split("] x")[1].split()[0])
                         for part in row.split("build.compile[")[1:])
    for name in ("build.trace x", "build.lower x", "build.compile["):
        assert name in row
    assert "kernels.import" not in row      # no kernel backend on the CPU
    spans = [s for s in tracectx.drain() if s["name"] == "build.compile"]
    assert len(spans) >= events             # those after the window too


def test_an_osu_cells_rehearsal_reports_the_four():
    p = run_py("--workload", SMALL, "--seed", "3000000019", "--seconds", "0.5",
               "--trace", "1", "--rehearse-cpu")
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.splitlines()
    for name in ("build_trace_s", "build_lower_s", "build_compile_s",
                 "build_cache_misses"):     # off the chip, a name and no value
        assert f"{name}: not measured" in lines
    assert not any(ln.startswith(("step_build_s", "kernel_traces"))
                   for ln in lines)
    result = json.loads(lines[-1])
    assert result["correct"] and not set(result["metrics"]) & set(MINE)
