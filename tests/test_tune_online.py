"""Online bandit autotuner (tpu_mpi.tune_online) + fleet database.

The lockstep-safety contract under test: exploration is a deterministic
function of rank-uniform values (per-rank call counters, a shared seed,
CRC32 arm choice), so every rank of a communicator observes the IDENTICAL
algorithm sequence — selection divergence must remain impossible with the
bandit live. The convergence test slows one arm with the latency shim
(TPU_MPI_TUNE_SHIM) and asserts the hot-swapped table abandons it within
one run, with per-call Event.algo agreement across ranks.
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from tpu_mpi import config, perfvars, tune, tune_online  # noqa: E402


def _reload(monkeypatch, **env):
    for k, v in env.items():
        monkeypatch.setenv(k, str(v))
    config.load(refresh=True)
    perfvars.reset()
    tune_online.reset()


@pytest.fixture(autouse=True)
def _clean_state():
    yield
    for k in ("TPU_MPI_TUNE_EXPLORE", "TPU_MPI_TUNE_SWAP_PERIOD",
              "TPU_MPI_TUNE_MIN_SAMPLES", "TPU_MPI_TUNE_SEED",
              "TPU_MPI_TUNE_SHIM", "TPU_MPI_PVARS", "TPU_MPI_COLL_ALGO",
              "TPU_MPI_AUTO_ARM"):
        os.environ.pop(k, None)
    config.load(refresh=True)
    perfvars.reset()
    tune_online.reset()


# ---------------------------------------------------------------------------
# Engine gating
# ---------------------------------------------------------------------------

def test_state_is_none_when_exploration_off(monkeypatch):
    _reload(monkeypatch)
    assert tune_online.state() is None          # default: knob unset
    _reload(monkeypatch, TPU_MPI_TUNE_EXPLORE="0")
    assert tune_online.state() is None          # explicit zero
    _reload(monkeypatch, TPU_MPI_TUNE_EXPLORE="0.25")
    assert tune_online.state() is not None
    # generation-cached: a reload with the knob cleared drops the bandit
    monkeypatch.delenv("TPU_MPI_TUNE_EXPLORE")
    config.load(refresh=True)
    assert tune_online.state() is None


def test_reconfigure_clamps_knobs(monkeypatch):
    _reload(monkeypatch, TPU_MPI_TUNE_EXPLORE="7.5",
            TPU_MPI_TUNE_SWAP_PERIOD="0", TPU_MPI_TUNE_MIN_SAMPLES="-3")
    st = tune_online.state()
    assert st.eps == 1.0
    assert st.swap_period == 1
    assert st.min_samples == 1


# ---------------------------------------------------------------------------
# Thread-tier lockstep: identical schedules, counters, and hot-swap table
# ---------------------------------------------------------------------------

def _spmd_explore_run(nprocs=4, rounds=40):
    from tpu_mpi.testing import run_spmd

    def body():
        import tpu_mpi as MPI
        comm = MPI.COMM_WORLD
        x = np.arange(8, dtype=np.float32)
        for _ in range(rounds):
            out = MPI.Allreduce(x, MPI.SUM, comm)
            assert np.allclose(out, x * MPI.Comm_size(comm))
            MPI.Barrier(comm)
        snap = perfvars.snapshot()
        ex = snap["comms"][0]["explore"]
        return (MPI.Comm_rank(comm), ex, dict(tune_online.table() or {}))

    return run_spmd(body, nprocs, init=True, timeout=120.0)


def test_thread_tier_lockstep_counters_and_swap(monkeypatch):
    # auto-arm off: this test pins down the raw decision-point counters,
    # and an auto-armed loop (the ISSUE-11 default) stops reaching the
    # bandit after the arming threshold — see test_auto_arm_* below for
    # the combined contract
    _reload(monkeypatch, TPU_MPI_PVARS="1", TPU_MPI_TUNE_EXPLORE="0.25",
            TPU_MPI_TUNE_SWAP_PERIOD="16", TPU_MPI_TUNE_MIN_SAMPLES="2",
            TPU_MPI_AUTO_ARM="0")
    res = sorted(_spmd_explore_run())
    # every rank went through the decision point the same number of times
    # and explored exactly the deterministic-fraction share of them
    first = res[0][1]
    assert first["calls"] == 80 and first["explored"] == 20
    assert first["fraction"] == 0.25
    assert first["table_swaps"] >= 1
    for _, ex, table in res[1:]:
        assert ex == first
        assert table == res[0][2]
    # the swap installed a live table select() now serves from
    assert res[0][2], "hot-swap produced no online table"
    assert ("allreduce", 4) in res[0][2] or ("barrier", 4) in res[0][2]


def test_forced_pin_suppresses_exploration(monkeypatch):
    _reload(monkeypatch, TPU_MPI_PVARS="1", TPU_MPI_TUNE_EXPLORE="0.5",
            TPU_MPI_COLL_ALGO="allreduce=star,barrier=star")
    res = sorted(_spmd_explore_run(rounds=20))
    for _, ex, _table in res:
        # pinned collectives never reach the bandit: no decisions, no
        # exploration — the pin is a debugging contract
        assert ex["calls"] == 0 and ex["explored"] == 0


# ---------------------------------------------------------------------------
# Procs-tier convergence: the shimmed arm is abandoned, ranks agree per call
# ---------------------------------------------------------------------------

def _run_procs(body: str, nprocs: int = 2, timeout: float = 240.0, env=None):
    script = textwrap.dedent(body)
    path = os.path.join("/tmp", f"tpu_mpi_online_{abs(hash(body)) % 10**8}.py")
    with open(path, "w") as f:
        f.write(f"import sys; sys.path.insert(0, {REPO!r})\n" + script)
    full = dict(os.environ)
    for k in ("TPU_MPI_PROC_RANK", "TPU_MPI_COLL_ALGO", "TPU_MPI_TUNE_TABLE",
              "TPU_MPI_TUNE_DB"):
        full.pop(k, None)
    full.update(env or {})
    return subprocess.run(
        [sys.executable, "-m", "tpu_mpi.launcher", "-n", str(nprocs),
         "--procs", "--sim", "1", "--timeout", str(timeout - 20), path],
        capture_output=True, text=True, timeout=timeout, env=full, cwd=REPO)


_CONVERGENCE_BODY = """
    import json
    import numpy as np
    import tpu_mpi as MPI
    from tpu_mpi import perfvars, tune_online
    from tpu_mpi._runtime import current_env
    from tpu_mpi.analyze import events as _ev

    MPI.Init()
    comm = MPI.COMM_WORLD
    rank, size = MPI.Comm_rank(comm), MPI.Comm_size(comm)
    x = (np.arange(256, dtype=np.float64) % 17) + rank   # 2048 B payload

    for i in range(200):
        out = MPI.Allreduce(x, MPI.SUM, comm)

    ctx, wrank = current_env()
    tr = _ev.tracer_for(ctx)
    algos = [(e.op, e.algo) for e in tr.events(wrank)
             if e.kind == "coll" and e.op.startswith("Allreduce")]
    snap = perfvars.snapshot()["comms"][0]
    table = {f"{c}.n{n}": ent for (c, n), ent in
             (tune_online.table() or {}).items()}
    with open(f"/tmp/tpu_mpi_conv_rank{rank}.json", "w") as f:
        json.dump({"algos": algos, "explore": snap["explore"],
                   "table": table}, f)
    print(f"CONV-OK-{rank}")
    MPI.Finalize()
"""


def test_bandit_convergence_abandons_shimmed_arm():
    # the heuristic's steady pick for a 2 KiB same-host allreduce is the
    # shm fold; the shim makes that arm deterministically lose by 3 ms, so
    # the online table must flip the steady selection away from it within
    # the 200-round run (swaps every 50 decisions)
    for r in range(2):
        path = f"/tmp/tpu_mpi_conv_rank{r}.json"
        if os.path.exists(path):
            os.unlink(path)
    res = _run_procs(_CONVERGENCE_BODY, nprocs=2, env={
        "TPU_MPI_PVARS": "1", "TPU_MPI_TRACE": "1",
        "TPU_MPI_TUNE_EXPLORE": "0.5", "TPU_MPI_TUNE_SWAP_PERIOD": "50",
        "TPU_MPI_TUNE_MIN_SAMPLES": "3", "TPU_MPI_TUNE_SEED": "7",
        "TPU_MPI_TUNE_SHIM": "allreduce:shm=3000"})
    assert res.returncode == 0, res.stderr[-4000:]
    dumps = []
    for r in range(2):
        with open(f"/tmp/tpu_mpi_conv_rank{r}.json") as f:
            dumps.append(json.load(f))
    # Event.algo agreement: both ranks observed the bitwise-identical
    # per-call algorithm sequence — selection divergence is impossible
    assert dumps[0]["algos"] == dumps[1]["algos"]
    assert len(dumps[0]["algos"]) == 200
    # exploration actually happened, in lockstep, and the table swapped
    assert dumps[0]["explore"] == dumps[1]["explore"]
    assert dumps[0]["explore"]["explored"] > 0
    assert dumps[0]["explore"]["table_swaps"] >= 1
    # both ranks derived the identical table, and it abandoned the
    # shimmed steady arm for the 2 KiB cell
    assert dumps[0]["table"] == dumps[1]["table"]
    ladder = dumps[0]["table"].get("allreduce.n2")
    assert ladder, dumps[0]["table"]
    picked = None
    for th, algo in sorted(map(tuple, ladder), reverse=True):
        if 2048 >= th:
            picked = algo
            break
    assert picked is not None and picked != "shm", ladder
    # and the post-swap steady traffic follows the flip: the tail of the
    # algo sequence must be dominated by non-shm selections
    tail = [a for _, a in dumps[0]["algos"][-50:]]
    assert tail.count("shm") < len(tail) / 2, tail[-20:]


# ---------------------------------------------------------------------------
# Noise guard (tune --from-pvars min-samples)
# ---------------------------------------------------------------------------

def _fake_record(cells):
    """A pvar-dump record with the given (coll, algo, nbytes, count) cells."""
    return {"_path": "fake.json", "kind": "tpu_mpi-pvars", "comms": [{
        "size": 4,
        "times": [{"coll": c, "algo": a, "nbytes": b, "count": n,
                   "total_s": n * 1e-4, "min_s": 1e-4, "max_s": 1e-4}
                  for c, a, b, n in cells]}]}


def test_rows_from_pvars_noise_guard():
    rec = _fake_record([("allreduce", "star", 1024, 20),
                        ("allreduce", "ring", 1024, 3),      # under-sampled
                        ("barrier", "shm", 0, 12)])
    skipped = []
    rows = tune.rows_from_pvars([rec], min_samples=8, skipped=skipped)
    kept = {(r["coll"], r["algo"]) for r in rows}
    assert kept == {("allreduce", "star"), ("barrier", "shm")}
    assert skipped == [("allreduce", 4, 1024, "ring", 3)]
    # min_samples=1 keeps everything
    assert len(tune.rows_from_pvars([rec], min_samples=1)) == 3


def test_rows_from_pvars_drops_internal_rendezvous():
    rec = _fake_record([("tuneswap", "star", 0, 50),
                        ("allreduce", "star", 64, 50)])
    rows = tune.rows_from_pvars([rec], min_samples=1)
    assert [r["coll"] for r in rows] == ["allreduce"]


# ---------------------------------------------------------------------------
# Fleet database: merge round-trip, weighting, provenance
# ---------------------------------------------------------------------------

def _write_dump(path, rank, cells):
    rec = _fake_record(cells)
    rec["rank"] = rank
    with open(path, "w") as f:
        json.dump(rec, f)


def test_fleet_merge_round_trip(tmp_path, monkeypatch):
    # >= 3 per-rank dumps: star is slow everywhere, ring fast at the bulk
    # cell; one rank contributes an under-sampled rdouble cell that the
    # min-samples guard must hold out of the ladder
    for r in range(3):
        _write_dump(tmp_path / f"pvars-rank{r}.json", r, [
            ("allreduce", "star", 1024, 10),
            ("allreduce", "ring", 1024, 10),
            ("allreduce", "rdouble", 1024, 1)])
    # make ring win: rewrite its mean via raw records (star 100us, ring
    # 10us per op)
    for r in range(3):
        p = tmp_path / f"pvars-rank{r}.json"
        rec = json.load(open(p))
        for t in rec["comms"][0]["times"]:
            t["total_s"] = (t["count"] * 1e-5 if t["algo"] == "ring"
                            else t["count"] * 1e-4)
        json.dump(rec, open(p, "w"))
    # a measured v1 table supplies ladders for keys the samples miss
    table_path = tmp_path / "measured.toml"
    tune.write_table(str(table_path), {("barrier", 8): [(0, "dissemination")]})

    db_path = tmp_path / "fleet-db.toml"
    rec = tune.merge_db(str(db_path),
                        [str(tmp_path / f"pvars-rank{r}.json")
                         for r in range(3)],
                        [str(table_path)], min_samples=8)
    assert rec["schema"] == 2
    assert rec["skipped_cells"] == 1                  # the rdouble cell
    assert len(rec["provenance"]) == 4                # 3 dumps + 1 table
    assert {p["kind"] for p in rec["provenance"]} == {"pvars", "table"}

    # the DB is a loadable v1 table: samples say ring, overlay fills n8
    loaded = tune.load_table(str(db_path))
    assert tune._table_lookup(loaded, "allreduce", 4, 1024) == "ring"
    assert tune._table_lookup(loaded, "barrier", 8, None) == "dissemination"

    # select() serves from it through config.tune_db
    monkeypatch.setenv("TPU_MPI_TUNE_DB", str(db_path))
    config.load(refresh=True)
    assert tune.select("allreduce", 4, 1024, commutative=True,
                       elementwise=True) == "ring"
    # nearest-nranks interpolation clamps at the DB's measured edges
    assert tune.select("allreduce", 2, 1024, commutative=True,
                       elementwise=True) == "ring"
    assert tune.select("allreduce", 64, 1024, commutative=True,
                       elementwise=True) == "ring"

    # re-merging the same dumps doubles the sample counts (count-weighted
    # accumulation) without changing the ladders
    rec2 = tune.merge_db(str(db_path),
                         [str(tmp_path / "pvars-rank0.json")], [])
    cell = [r for r in rec2["rows"]
            if r["algo"] == "ring" and r["bytes"] == 1024]
    assert cell and cell[0]["count"] == 40            # 30 merged + 10 new
    tune._table_cache.clear()
    assert tune._table_lookup(tune.load_table(str(db_path)),
                              "allreduce", 4, 1024) == "ring"


def test_merge_cli_and_online_report(tmp_path):
    for r in range(3):
        _write_dump(tmp_path / f"pvars-rank{r}.json", r,
                    [("allreduce", "star", 64, 10)])
    db = tmp_path / "db.toml"
    rc = tune.main(["merge", str(tmp_path), "-o", str(db),
                    "--min-samples", "2", "--topology", "test-fabric"])
    assert rc == 0
    text = open(db).read()
    assert "schema = 2" in text
    assert 'topology = "test-fabric"' in text
    assert "[provenance.s0]" in text
    assert "[samples.allreduce.n4.star]" in text
    # the online report reads the same dumps
    rc = tune.main(["--online", str(tmp_path),
                    "--json", str(tmp_path / "online.json")])
    assert rc == 0
    rep = json.load(open(tmp_path / "online.json"))
    assert rep["bench"] == "tune_online_report"
    assert rep["arms"] and rep["arms"][0]["coll"] == "allreduce"


# ---------------------------------------------------------------------------
# Auto-arm x exploration (ISSUE 11): armed plans never reach the bandit,
# and the combination keeps Event.algo sequences rank-identical
# ---------------------------------------------------------------------------

def test_auto_arm_skips_exploration_in_lockstep(monkeypatch):
    # auto-arm ON (the default) with the bandit live: the plain Allreduce
    # loop stops reaching the decision point once armed, on every rank at
    # the same call — counters stay rank-identical and strictly below the
    # unarmed figure (80 calls for 40 allreduce+barrier rounds)
    _reload(monkeypatch, TPU_MPI_PVARS="1", TPU_MPI_TUNE_EXPLORE="0.25",
            TPU_MPI_TUNE_SWAP_PERIOD="16", TPU_MPI_TUNE_MIN_SAMPLES="2",
            TPU_MPI_AUTO_ARM="1", TPU_MPI_AUTO_ARM_THRESHOLD="4")
    from tpu_mpi.overlap import plans
    res = sorted(_spmd_explore_run())
    first = res[0][1]
    for _, ex, _table in res[1:]:
        assert ex == first          # rank-identical counters
    # barriers keep exploring every round; allreduce stopped at the arm
    assert first["calls"] < 80, first
    assert plans.stats()["auto"]["arms"] >= 1


def test_auto_arm_traced_algo_sequences_rank_identical():
    # tracing + exploration + auto-arm all on: tracing demotes auto-armed
    # rounds to the fully-evented generic lane on EVERY rank (trace
    # enablement is config-global), so the bandit runs in lockstep and
    # per-call Event.algo sequences stay bitwise rank-identical
    body = """
    import numpy as np
    import tpu_mpi as MPI
    from tpu_mpi._runtime import current_env
    from tpu_mpi.analyze import events as _ev

    MPI.Init()
    comm = MPI.COMM_WORLD
    rank = MPI.Comm_rank(comm)
    x = (np.arange(64, dtype=np.float64) % 5) + rank
    for i in range(60):
        out = MPI.Allreduce(x, MPI.SUM, comm)
    ctx, wrank = current_env()
    tr = _ev.tracer_for(ctx)
    algos = [(e.op, e.algo) for e in tr.events(wrank)
             if e.kind == "coll" and e.op.startswith("Allreduce")]
    import json
    with open(f"/tmp/tpu_mpi_autoarm_rank{rank}.json", "w") as f:
        json.dump(algos, f)
    print(f"AA-OK-{rank}")
    MPI.Finalize()
    """
    for r in range(2):
        p = f"/tmp/tpu_mpi_autoarm_rank{r}.json"
        if os.path.exists(p):
            os.unlink(p)
    res = _run_procs(body, nprocs=2, env={
        "TPU_MPI_TRACE": "1", "TPU_MPI_TUNE_EXPLORE": "0.5",
        "TPU_MPI_TUNE_SEED": "11", "TPU_MPI_AUTO_ARM": "1",
        "TPU_MPI_AUTO_ARM_THRESHOLD": "4"})
    assert res.returncode == 0, res.stderr[-4000:]
    dumps = []
    for r in range(2):
        with open(f"/tmp/tpu_mpi_autoarm_rank{r}.json") as f:
            dumps.append(json.load(f))
    assert dumps[0] == dumps[1]
    assert len(dumps[0]) == 60
