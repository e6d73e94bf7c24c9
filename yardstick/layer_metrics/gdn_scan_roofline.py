"""gdn_scan_roofline (%): the delta-rule scan against the chip. Least time
= the larger of (a) the matrix FLOPs the scan needs in its chunked form at
the model's chunk (lm_gdn_flops.scan_chunked_flops: K K^T, Q K^T, the
triangular system by substitution, the three products with the state and
the masked scores' product, forward, and twice that backward) over
`bf16_flops` of peaks.json and (b) its least bytes (lm_gdn_flops.
scan_least_bytes: q, k, v, g, beta in and o out once forward; those, do in
and the five gradients out once backward) over `hbm_bytes_per_s`; x the
delta-rule layers; divided by the device time under `layer_<i>/mixer/scan`.
Both counts are of the mathematics and not of the form that runs, so a later
kernel is read against the same work. The reader's line says which of the
two bounds. A reading over 100 means a count or the time is wrong."""

from yardstick import gdn_scope_reduce


def read(run):
    ms = gdn_scope_reduce.per_step_ms(run)
    scan = run.facts.get("scan")
    if ms is None or run.peaks is None or not scan or ms["scan"] <= 0.0 \
            or "chunked_flops" not in scan:
        return None
    by_flops = sum(scan["chunked_flops"].values()) / run.peaks["bf16_flops"]
    by_bytes = sum(scan["least_bytes"].values()) / run.peaks["hbm_bytes_per_s"]
    run.row(f"delta scan, least ms a layer: {by_flops * 1e3:.3f} by its "
            f"matrix FLOPs, {by_bytes * 1e3:.3f} by its bytes; "
            f"{scan['layers']} layers took {ms['scan']:.3f} ms")
    return 100.0 * scan["layers"] * max(by_flops, by_bytes) * 1e3 / ms["scan"]
