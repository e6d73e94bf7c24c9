"""row_sum_product_share (%): of the sums of rows into indexed places counted
in the programs traced before the window (a held expert layer's combine, the
gather of its dispatch, whose gradient is such a sum, and the embedding's
gather likewise; once for each trace that holds one: the step's, and the
generator's forward programs'), how many the program lowered as the product
with a 0/1 matrix on the MXU (`pallas_kernels.grouped_row_sums` behind
`parallel.ep.sum_rows` / `rows_at`) and not as XLA's scatter-add. The
process-wide pair `row_sum_lowerings` of `perfvars.snapshot()` at the
window's begin, after warm-up has compiled everything the window runs:
`product` over `product` + `scatter`. 100 where the backend and the shapes
select the product, 0 where they leave the sum to the scatter-add. A program
without the counter (the parent of the PR that added it) has nothing to
read."""


def read(run):
    built = run.counters.get("begin", {}).get("row_sum_lowerings")
    if not built:
        return None
    product, scatter = int(built.get("product", 0)), int(built.get("scatter", 0))
    if not product + scatter:
        return None
    return 100.0 * product / (product + scatter)
