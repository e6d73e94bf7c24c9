"""armed_share (%): of the window's collective calls, over all ranks, how
many ran on the auto-armed registered lane. Source: the per-signature `hits`
of `overlap.plans.stats()["auto"]["signatures"]` (the `plan_cache` block of
the pvar snapshot), as a delta over the window
(the aggregate `auto.hits` tolerates lost updates on the front door,
collective._auto_hot_run, so it is not read)."""

EXACT_COUNT = True


def hits(snapshot: dict) -> int:
    sigs = snapshot["plan_cache"]["auto"]["signatures"]
    return sum(int(s["hits"]) for s in sigs.values())


def read(run):
    calls = run.facts.get("ops", 0) * run.facts.get("ranks", 0)
    if not calls or "end" not in run.counters:
        return None
    armed = hits(run.counters["end"]) - hits(run.counters["begin"])
    return 100.0 * armed / calls
