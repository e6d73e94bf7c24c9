"""ssm_scan_roofline (%): the state-space scan against the chip's HBM
bandwidth. Least time = the scan's LEAST bytes (lm_ssm_flops.
scan_least_bytes: x, B, C, dt in and y out once forward; those, dy in and dx,
dB, dC, ddt out once backward; no state, no decay matrix, nothing twice) x
the state-space layers, over `hbm_bytes_per_s` of peaks.json; divided by the
device time under `layer_<i>/mixer/scan`. Bound by bandwidth: the scan's
products are about 2% of the step's FLOPs. No implementation can move less,
so a reading over 100 means the count or the time is wrong."""

from yardstick import ssm_scope_reduce


def read(run):
    ms = ssm_scope_reduce.per_step_ms(run)
    scan = run.facts.get("scan")
    if ms is None or run.peaks is None or not scan or ms["scan"] <= 0.0:
        return None
    least = scan["layers"] * sum(scan["least_bytes"].values())
    return 100.0 * least / run.peaks["hbm_bytes_per_s"] * 1e3 / ms["scan"]
