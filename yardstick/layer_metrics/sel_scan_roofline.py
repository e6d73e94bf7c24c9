"""sel_scan_roofline (%): the selective scan against the chip's HBM
bandwidth. Least time = the scan's LEAST bytes (lm_sambay_flops.
scan_least_bytes: x, dt, B, C in and y out once forward; those, dy in and dx,
ddt, dB, dC out once backward; no state, no decay, nothing twice) x the mamba
layers, over `hbm_bytes_per_s` of peaks.json; divided by the device time
under `layer_<i>/mixer/scan`. The scan's work is one exponential and a few
multiplications a token, channel and state index, on the VPU and the EUP,
for which peaks.json has no peak: so the share is of the one bound no
implementation passes, and says how far the recurrence is from being as
cheap as reading its operands. A reading over 100 means the count or the
time is wrong."""

from yardstick import sambay_scope_reduce


def read(run):
    ms = sambay_scope_reduce.per_step_ms(run)
    scan = run.facts.get("scan")
    if ms is None or run.peaks is None or not scan or ms["scan"] <= 0.0:
        return None
    least = scan["layers"] * sum(scan["least_bytes"].values())
    return 100.0 * least / run.peaks["hbm_bytes_per_s"] * 1e3 / ms["scan"]
