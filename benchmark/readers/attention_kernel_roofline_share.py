"""The attention kernels' share of their roofline, in %: the least time
the chip needs for one step's attention calls (``attention_cost``: the
operations of the key tiles WALKED over the bf16 peak, or the bytes over
the HBM bandwidth, whichever is larger: operations at this cell's
shapes) over the device seconds a step of the operations named
``causal_attention_forward*`` and ``causal_attention_backward*`` in the
trace. A forward kernel that runs twice a step (its output and
log-sum-exp not kept through the rematerialisation) counts its seconds
twice and its operations once. A trace without such operations (the scan,
another program) or a configuration without attention layers of this
kind gives nothing to read."""

from . import attention_cost

KERNELS = ("causal_attention_forward", "causal_attention_backward")


def kernel_seconds(trace) -> float:
    """Device seconds of the two kernels over the stretch that was read
    (the trace names an operation by its HLO text, ``%name.N = ...``)."""
    return sum(seconds for name, seconds in trace["ops"].items()
               if name.lstrip("%").startswith(KERNELS))


def read(context):
    trace, peaks = context["trace"], context["peaks"]
    config = context["cell"].config
    model = config["model"]
    if not trace or not trace.get("steps") or not peaks \
            or "sliding_window" not in model:
        return None
    measured = kernel_seconds(trace) / trace["steps"]
    if not measured:
        return None
    least, _ = attention_cost.step_seconds(
        model, config["per_chip_batch"], peaks)
    return 100.0 * least / measured
