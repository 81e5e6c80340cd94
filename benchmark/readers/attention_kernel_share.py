"""Share of the model's attention calls that run as the fused kernels in
the step program, in %: ``100 x attention_kernel_calls /
attention_calls`` off the window's ``fetch`` spans that carry both.
Constants of the lowered step program (the calls of
``dptpu.ops.attention`` in one forward pass, and those of them whose
shapes its kernels tile in a program lowered for a TPU), so every span
says the same and the last one is read: 100 where every call left the
scan, 0 where every one kept it. A program that does not count them, or
a model without such a call, gives nothing to read."""

from . import span_attrs


def read(context):
    spans = [s["attrs"] for s in span_attrs.carrying(context, "fetch",
                                                     "attention_calls")
             if "attention_kernel_calls" in s["attrs"]]
    if not spans or not spans[-1]["attention_calls"]:
        return None
    return 100.0 * spans[-1]["attention_kernel_calls"] \
        / spans[-1]["attention_calls"]
