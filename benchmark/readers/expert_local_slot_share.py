"""Share of all routed slots that fell on the experts held here, in %,
over the window's ``fetch`` spans that carry ``attrs.moe_local_slots``
and ``attrs.moe_slots``. 100 x held / routed experts is what uniform
routing gives, and what the family's ``train_flops`` assumes."""

from . import span_attrs


def read(context):
    fetches = [s["attrs"] for s in
               span_attrs.carrying(context, "fetch", "moe_local_slots")]
    slots = sum(a.get("moe_slots", 0) for a in fetches)
    if not slots:
        return None
    return 100.0 * sum(a["moe_local_slots"] for a in fetches) / slots
