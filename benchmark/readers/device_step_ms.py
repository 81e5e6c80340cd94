"""Device time of one step: the union of the busiest device's operation
intervals over the steps traced, in ms."""


def read(context):
    trace = context["trace"]
    if not trace or not trace["busiest"]["steps"]:
        return None
    dev = trace["busiest"]
    return dev["busy_ns"] / dev["steps"] / 1e6
