"""Share of the batches whose workers were all done when the loop came
to collect them (``ready`` of the ``collect`` spans), in %."""

from . import span_attrs


def read(context):
    return span_attrs.share_pct(context, "collect", "ready")
