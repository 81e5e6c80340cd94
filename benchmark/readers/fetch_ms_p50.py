"""Median ``fetch`` span of the window (the loop's lagged ``device_get``
of each print interval), in ms."""

from benchmark.lib import spans


def read(context):
    durations = spans.durations_ms(context["window"], "fetch")
    return spans.percentile(durations, 50.0) if durations else None
