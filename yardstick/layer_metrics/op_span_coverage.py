"""op_span_coverage (%): how much of the `op` bracket its named children
cover: (front_door + lock + rdv_skew + rdv_fold + rdv_wake + fold_dispatch
+ copyout) seconds over `op` seconds, over the sampled ops of the profiled
interval, all ranks. What is left open is code between the spans. The
reader prints the parts, the open rest and the mean `op` bracket as one
row."""

from yardstick import span_reduce

prepare = span_reduce.prepare


def read(run):
    row = span_reduce.parts_row(run)
    if row is None or row["op"] <= 0.0:
        return None
    return 100.0 * (row["op"] - row["(open)"]) / row["op"]
