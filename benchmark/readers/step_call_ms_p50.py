"""Median length of the loop's dispatch call (the ``step`` span), in ms."""

from benchmark.lib import spans

from . import span_attrs


def read(context):
    calls = span_attrs.carrying(context, "step", "cpu_s")
    if not calls:
        return None
    return spans.percentile([s["dur_s"] * 1e3 for s in calls], 50.0)
