"""Mean ms of ``ShardedLSM.aggregate_many`` per step that has
aggregates (host clock)."""


def read(ctx):
    b = [x for x in ctx["batches"] if x.n_aggs]
    return sum(x.agg_s for x in b) / len(b) * 1e3 if b else None
