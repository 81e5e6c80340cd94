"""Share of the expert layers' runs (a layer counted once a step) whose
held slots fitted the layer's compact buffer, in %: ``100 x
moe_compact_layers / moe_layers`` over the window's ``fetch`` spans that
carry both. The rest took the worst-case buffer (every ``tokens x k``
slot) for that step: no token is dropped on either path, the step only
moves more rows. 100 where every layer of every step was compact. A
program that does not count them, or a model without experts, gives
nothing to read."""

from . import span_attrs


def read(context):
    fetches = [s["attrs"] for s in
               span_attrs.carrying(context, "fetch", "moe_compact_layers")
               if "moe_layers" in s["attrs"]]
    layers = sum(a["moe_layers"] for a in fetches)
    if not layers:
        return None
    return 100.0 * sum(a["moe_compact_layers"] for a in fetches) / layers
