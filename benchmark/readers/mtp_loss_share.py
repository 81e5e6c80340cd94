"""The multi-token-prediction loss's share of the objective, in %:
``100 x mtp_loss_weight x mtp_loss / loss`` over the window's ``fetch``
spans that carry ``attrs.mtp_loss`` (the module's loss before its weight)
beside ``attrs.loss`` (the whole objective), each the mean over the steps
the fetch read. The weight is the configuration's ``mtp_loss_weight``. A
guard: a step that left the module out would be faster and carry no such
attribute. A program that does not count it gives nothing to read."""

from . import span_attrs


def read(context):
    weight = context["cell"].config.get("mtp_loss_weight")
    spans = [s["attrs"] for s in span_attrs.carrying(context, "fetch",
                                                     "mtp_loss")
             if s["attrs"].get("loss")]
    if weight is None or not spans:
        return None
    return 100.0 * float(weight) * sum(a["mtp_loss"] for a in spans) \
        / sum(a["loss"] for a in spans)
