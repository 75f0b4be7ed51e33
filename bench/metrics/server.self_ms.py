"""Mean ms per ``ScanServer.step`` outside the two engine calls: the
benchmark's span around ``step`` minus its spans around
``filter_many`` and ``aggregate_many`` (host clock)."""


def read(ctx):
    b = ctx["batches"]
    if not b:
        return None
    return sum((x.t1 - x.t0 - x.filter_s - x.agg_s) for x in b) / len(b) * 1e3
