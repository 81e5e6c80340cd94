"""Median ``h2d`` span of the window (the prefetcher's ``put`` of one
batch: the call, not the copy's end on the device), in ms."""

from benchmark.lib import spans

from . import span_attrs


def read(context):
    puts = span_attrs.carrying(context, "h2d", "bytes")
    if not puts:
        return None
    return spans.percentile([s["dur_s"] * 1e3 for s in puts], 50.0)
