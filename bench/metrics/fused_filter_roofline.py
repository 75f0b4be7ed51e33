"""The fused filter kernel's share of its HBM roofline, in %: the bytes
any implementation must read and write for the window's launches
(``roofline_work.filter_launch_bytes``) over the chip's HBM bandwidth,
divided by the device time of the ``fused_zone_filter_2d`` programs in
the trace.  Nothing when the trace has none, or when the shards'
launches differ so that bytes cannot be given per launch."""

from bench import roofline_work


def read(ctx):
    return roofline_work.roofline_pct(ctx, "filter")
