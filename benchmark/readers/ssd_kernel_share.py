"""Share of the model's state-space scans that run as a fused kernel in
the step program, in %: ``100 x ssd_kernel_calls / ssd_calls`` off the
window's ``fetch`` spans that carry both. Constants of the lowered step
program (the calls of ``dptpu.ops.ssd`` in one forward pass, and those of
them that a kernel takes in a program lowered for a TPU), so every span
says the same and the last one is read: 0 while the chunked scan is XLA
operations. A program that does not count them, or a model without the
scan, gives nothing to read."""

from . import span_attrs


def read(context):
    spans = [s["attrs"] for s in span_attrs.carrying(context, "fetch",
                                                     "ssd_calls")
             if "ssd_kernel_calls" in s["attrs"]]
    if not spans or not spans[-1]["ssd_calls"]:
        return None
    return 100.0 * spans[-1]["ssd_kernel_calls"] / spans[-1]["ssd_calls"]
