"""The executor's ``"merge"`` stage (visibility merge: lexsort and
shadow check), ms per step with filters, summed over shards: the delta
of ``filter_stats`` over the window."""


def read(ctx):
    n = sum(1 for x in ctx["batches"] if x.n_filters)
    if not n:
        return None
    return (ctx["after"]["merge_s"] - ctx["before"]["merge_s"]) / n * 1e3
