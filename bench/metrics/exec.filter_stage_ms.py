"""The executor's ``"filter"`` stage (kernel launch, readback, bitmap
expansion, candidate gather and decode), ms per step with filters,
summed over shards: the delta of ``filter_stats`` over the window."""


def read(ctx):
    n = sum(1 for x in ctx["batches"] if x.n_filters)
    if not n:
        return None
    return (ctx["after"]["filter_s"] - ctx["before"]["filter_s"]) / n * 1e3
