"""rendezvous_wait_share (%): share of the window that a rank thread spent
blocked in the collective channel waiting for its peers (or for the last
arriver's fold). Source: the pvar phase time `phase_s["rendezvous"]`
(tpu_mpi/_runtime.py CollectiveChannel.run), delta over the window, summed
over ranks, over ranks x window. A host-clock span inside the program: it
advances on the armed lane too (checked in PR 22). A run in which it does
not advance reports nothing."""


def waited(snapshot: dict) -> float:
    return sum(c["phase_s"].get("rendezvous", 0.0)
               for c in snapshot["comms"])


def read(run):
    ranks = run.facts.get("ranks", 0)
    if not ranks or "end" not in run.counters or not run.window_s:
        return None
    delta = waited(run.counters["end"]) - waited(run.counters["begin"])
    if delta <= 0.0:
        return None
    return 100.0 * delta / (ranks * run.window_s)
