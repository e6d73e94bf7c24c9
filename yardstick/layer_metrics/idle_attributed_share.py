"""idle_attributed_share (%): of the busiest chip's idle seconds in the
profiled interval, the share that the program's own spans can name. A gap
that ends where a fold's device event starts has one cause, the thread that
launched that fold: the round's last arriver. Its gap is cut, in order,
into `outside the program` (before its `op` began), `front_door`, `lock`
(to the start of its dispatch), `fold_dispatch`, and `launch` (dispatch
returned, the device had not started). A gap that ends anywhere else (a
readback's slice, the interval's end) has no such owner. The metric is the
idle seconds before folds over all idle seconds. The cut itself is made on
the rounds whose spans were sampled (one in 8, yardstick/span_reduce.py);
the reader prints their seconds per name and per round. Also read under
`idle_attributed_share.<tag>`."""

from yardstick import span_reduce

prepare = span_reduce.prepare


def read(run):
    summary = span_reduce.summarize(run)
    if summary is None:
        return None
    named, lags = span_reduce.attribute_gaps(summary) or (None, None)
    if named is None:
        return None
    cut = len(lags)
    idle = sum(named.values())
    if idle <= 0.0:
        return None
    run.row(f"idle seconds of the busiest chip, {idle:.6f} in all: "
            f"{named[span_reduce.NOT_A_FOLD]:.6f} end at no fold, "
            f"{named[span_reduce.UNSAMPLED]:.6f} at the fold of a round not "
            f"sampled; before the folds of {cut} sampled rounds, by the last "
            "arriver's spans, seconds (us per round): "
            + "  ".join(f"{k} {named[k]:.6f} ({named[k] / cut * 1e6:.1f})"
                        for k in span_reduce.GAP_NAMES)
            + "; the device started after the dispatch began by "
            f"{min(lags) * 1e6:.1f} us at least, "
            f"{sorted(lags)[cut // 2] * 1e6:.1f} us in the median")
    return 100.0 * (idle - named[span_reduce.NOT_A_FOLD]) / idle
