"""xchip_bytes_per_op (bytes): bytes per collective whose source and
destination chips differ: operands copied to the folding chip and results
copied back. The pvar counter `xchip_bytes` (every rank and communicator),
as a delta over the window, over the window's ops. The star through rank
0's chip moves (n - 1) operands in and (n - 1) results out: 6 x payload
where four ranks sit on four chips, 0 on one chip. An exact count."""

EXACT_COUNT = True      # repeats exactly, so a CPU rehearsal may report it


def moved(snapshot: dict):
    comms = snapshot.get("comms", [])
    if not comms or any("xchip_bytes" not in c for c in comms):
        return None
    return sum(int(c["xchip_bytes"]) for c in comms)


def read(run):
    ops = run.facts.get("ops", 0)
    if not ops or "end" not in run.counters:
        return None
    begin, end = moved(run.counters["begin"]), moved(run.counters["end"])
    if begin is None or end is None:
        return None
    per_op, rest = divmod(end - begin, ops)
    return per_op if rest == 0 else (end - begin) / ops
