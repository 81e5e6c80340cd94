"""Per step, the time the busiest device spent in collective operations
while no compute operation ran on it, in ms. Nothing where the trace holds
no collective (one chip)."""


def read(context):
    trace = context["trace"]
    if not trace:
        return None
    dev = trace["busiest"]
    if not dev["has_collectives"] or not dev["steps"]:
        return None
    return dev["collective_exposed_ns"] / dev["steps"] / 1e6
