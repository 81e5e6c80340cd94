"""Tokens the expert layers dropped, summed over the window's ``fetch``
spans that carry ``attrs.moe_dropped``. The layer has no capacity limit:
0, until a capacity scheme moves it."""

from . import span_attrs


def read(context):
    dropped = span_attrs.values(context, "fetch", "moe_dropped")
    return float(sum(dropped)) if dropped else None
