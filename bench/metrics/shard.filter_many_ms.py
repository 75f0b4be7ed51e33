"""Mean ms of ``ShardedLSM.filter_many`` per step that has filters: the
shard scatter-gather with everything under it (host clock)."""


def read(ctx):
    b = [x for x in ctx["batches"] if x.n_filters]
    return sum(x.filter_s for x in b) / len(b) * 1e3 if b else None
