"""Wall time the feed's workers took per row they made (``wall_s`` over
``rows`` of the ``collect`` spans), in microseconds. Against
``feed_row_cpu_us``: how long a worker waits for each row it makes."""

from . import span_attrs


def read(context):
    return span_attrs.per_row_us(context, "wall_s")
