"""Median number of earlier steps still in flight on the device when the
loop dispatched the next (``inflight`` of the ``step`` spans)."""

from benchmark.lib import spans

from . import span_attrs


def read(context):
    depths = span_attrs.values(context, "step", "inflight")
    return float(spans.percentile(depths, 50.0)) if depths else None
