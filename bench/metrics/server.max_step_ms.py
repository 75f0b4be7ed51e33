"""The longest ``ScanServer.step`` of the window, in ms (host clock):
a stall of the host or the device shows here before it shows in the
tail of every request behind it."""


def read(ctx):
    b = ctx["batches"]
    return max(x.t1 - x.t0 for x in b) * 1e3 if b else None
