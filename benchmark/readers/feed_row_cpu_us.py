"""CPU time the feed's workers spent per row they made (``cpu_s`` over
``rows`` of the ``collect`` spans), in microseconds. Nothing where the
workers are processes: their message carries no CPU time."""

from . import span_attrs


def read(context):
    return span_attrs.per_row_us(context, "cpu_s")
