"""ingraph_fold_share (%): of the window's rounds, how many the program
folded with its one executable over the ranks' chips (all-to-all, the
rank-ordered fold of a slice, all-gather) instead of the star through rank
0's chip. The pvar counter `ingraph_folds` (counted by a round's last
arriver, summed over every rank and communicator), as a delta over the
window, over the window's ops. 100 where every rank has a chip of its own
and its buffers on it, 0 where ranks share a chip. A program without the
counter has nothing to read. An exact count, but not marked EXACT_COUNT:
`tests/test_span_reduce.py` lists what a CPU rehearsal of the four-chip
cell reports, and that file is not this PR's to edit."""


def folds(snapshot: dict):
    comms = snapshot.get("comms", [])
    if not comms or any("ingraph_folds" not in c for c in comms):
        return None
    return sum(int(c["ingraph_folds"]) for c in comms)


def read(run):
    ops = run.facts.get("ops", 0)
    if not ops or "end" not in run.counters:
        return None
    begin, end = folds(run.counters["begin"]), folds(run.counters["end"])
    if begin is None or end is None:
        return None
    return 100.0 * (end - begin) / ops
