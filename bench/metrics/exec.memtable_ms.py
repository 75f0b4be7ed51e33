"""The executors' ``"memtable"`` stage (the memtable stack's newest
visible rows and the predicates over them), in the filters and in the
aggregates' general path: ms per step, summed over shards, the delta of
``filter_stats`` plus ``agg_stats`` over the window."""


def read(ctx):
    n = len(ctx["batches"])
    if not n:
        return None
    return (ctx["after"]["memtable_s"] - ctx["before"]["memtable_s"]) / n * 1e3
