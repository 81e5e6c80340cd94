"""Share of the steps before whose dispatch the loop had to wait for the
device, its run-ahead being bounded (``paced`` of the ``step`` spans),
in %."""

from . import span_attrs


def read(context):
    return span_attrs.share_pct(context, "step", "paced")
