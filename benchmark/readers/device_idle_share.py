"""Share of the traced stretch in which no operation ran on the device,
averaged over the chips, in %."""


def read(context):
    trace = context["trace"]
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
