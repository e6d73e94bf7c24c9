"""coll_latency_tail (us): the 99th percentile of the window's per-op
latencies (one sample per op index of the ops synced per op: the slowest
rank's call entered -> its result ready), the generator's own
`coll_latency_p99`, host clock. A per-layer metric and no end-to-end one
because its spread between runs of the same code went from 0.4% to 3.0%
from one set of six to the next (PR 22): no bound the contract admits is
both over twice and under eight times a spread that moves by a factor of
seven. The jitter a bulk-synchronous program pays at every step."""


def read(run):
    return run.results.get("metrics", {}).get("coll_latency_p99")
