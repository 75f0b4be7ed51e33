"""Mean requests per ``ScanServer.step`` in the window (count)."""


def read(ctx):
    b = ctx["batches"]
    return sum(x.n_filters + x.n_aggs for x in b) / len(b) if b else None
