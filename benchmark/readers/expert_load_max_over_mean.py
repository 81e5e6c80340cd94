"""Tokens at the busiest held expert over the mean held expert, a layer
a step, averaged over the window's ``fetch`` spans that carry the expert
layers' load (``attrs.moe_load_max``, ``attrs.moe_load_mean``: the loop
puts them there for a model with experts). 1 is an even load."""

from . import span_attrs


def read(context):
    fetches = [s["attrs"] for s in
               span_attrs.carrying(context, "fetch", "moe_load_max")
               if s["attrs"].get("moe_load_mean")]
    if not fetches:
        return None
    return sum(a["moe_load_max"] / a["moe_load_mean"]
               for a in fetches) / len(fetches)
