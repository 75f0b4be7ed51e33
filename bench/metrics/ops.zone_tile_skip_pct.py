"""Share of fused-filter tiles the zone maps pruned in the window, in %:
the delta of ``zone_tiles_skipped`` over ``zone_tiles_total``."""


def read(ctx):
    total = ctx["after"]["zone_tiles_total"] - ctx["before"]["zone_tiles_total"]
    skipped = (ctx["after"]["zone_tiles_skipped"]
               - ctx["before"]["zone_tiles_skipped"])
    return 100.0 * skipped / total if total else None
