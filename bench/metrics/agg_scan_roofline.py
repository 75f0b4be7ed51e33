"""The aggregate kernels' share of their HBM roofline, in %: one read
of the minimal code bits per launch (``roofline_work.agg_launch_bytes``)
over the chip's HBM bandwidth, divided by the device time of the
``fused_zone_agg_2d`` and ``zone_histogram_2d`` programs in the
trace."""

from bench import roofline_work


def read(ctx):
    return roofline_work.roofline_pct(ctx, "agg")
