"""Mean ms of ``ShardedLSM.put_batch`` per write batch applied in the
window, to its return (the group-commit acknowledgement; host clock)."""


def read(ctx):
    w = ctx["writer"]
    return sum(w.put_s) / len(w.put_s) * 1e3 if w.put_s else None
