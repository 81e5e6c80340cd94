"""Share of the steps whose batch had landed on the device when the step
was dispatched (``input_ready`` of the ``step`` spans), in %."""

from . import span_attrs


def read(context):
    return span_attrs.share_pct(context, "step", "input_ready")
