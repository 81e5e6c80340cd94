"""Share of the causal triangle's key tiles that the model's attention
calls walk, in %: ``100 x attention_tiles / attention_tiles_causal`` off
the window's ``fetch`` spans that carry both. Constants of the lowered
step program (the tiles ``dptpu.ops.attention`` walks in one forward pass
over all calls, and what the same calls would walk without a window), so
every span says the same and the last one is read: 100 for a model
without windows, or where a window is masked and not skipped. A program
that does not count them gives nothing to read."""

from . import span_attrs


def read(context):
    spans = [s["attrs"] for s in span_attrs.carrying(context, "fetch",
                                                     "attention_tiles")
             if s["attrs"].get("attention_tiles_causal")]
    if not spans:
        return None
    return 100.0 * spans[-1]["attention_tiles"] \
        / spans[-1]["attention_tiles_causal"]
