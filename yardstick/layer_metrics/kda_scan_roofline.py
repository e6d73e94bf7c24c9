"""kda_scan_roofline (%): the KDA layers' scan against the chip. Least time
a layer = the larger of the scan's matrix FLOPs in its chunked form over
`bf16_flops` and its least bytes over `hbm_bytes_per_s` of peaks.json
(lm_kda_flops.scan_chunked_flops, scan_least_bytes: counts of the
mathematics with a [tokens x heads x key width] float32 decay, whatever
implements it), forward and backward, x the KDA layers, divided by the
device time under `mixer/scan`. The row says which of the two bounds. The
plain path's loops and [chunk x chunk] temporaries read a few percent; a
kernel that keeps a chunk in VMEM reads against the same work. A reading
over 100 means the count or the time is wrong."""

from yardstick import kda_scope_reduce


def read(run):
    ms = kda_scope_reduce.per_step_ms(run)
    scan = run.facts.get("kda_scan")
    if ms is None or run.peaks is None or not scan or ms["scan"] <= 0.0:
        return None
    by_flops = sum(scan["chunked_flops"].values()) / run.peaks["bf16_flops"]
    by_bytes = sum(scan["least_bytes"].values()) / run.peaks["hbm_bytes_per_s"]
    run.row(f"kda scan, least ms a layer: {by_flops * 1e3:.3f} by its "
            f"matrix FLOPs, {by_bytes * 1e3:.3f} by its bytes (bound by its "
            f"{'FLOPs' if by_flops > by_bytes else 'bytes'}); "
            f"{scan['layers']} layers took {ms['scan']:.3f} ms")
    return 100.0 * scan["layers"] * max(by_flops, by_bytes) * 1e3 / ms["scan"]
