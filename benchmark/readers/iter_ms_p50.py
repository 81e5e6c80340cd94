"""Median ``iter`` span of the window, in ms."""

from benchmark.lib import spans


def read(context):
    durations = [s["dur_s"] * 1e3 for s in context["window"].iters]
    return spans.percentile(durations, 50.0) if durations else None
