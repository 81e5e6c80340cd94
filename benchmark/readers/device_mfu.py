"""Model FLOP/s utilization of the device step, in %: the operations the
forward and backward passes need for one chip's rows (from shapes, by the
configuration's reference module) over ``device_step_ms`` over the chip's
bf16 peak. The FLOP leg of the roofline only; recomputed operations do not
count. Host stalls are not in it: that is ``train_img_s_chip``'s business."""

from benchmark.lib import cells

from . import device_step_ms


def read(context):
    step_ms = device_step_ms.read(context)
    peaks = context["peaks"]
    if step_ms is None or not peaks:
        return None
    config = context["cell"].config
    flops = cells.reference(config).train_flops(
        config["model"], config["per_chip_batch"])
    return 100.0 * flops / (step_ms / 1e3) / peaks["bf16_flops_per_s"]
