"""Share of the window the loop spent waiting for its next batch, in %."""

from benchmark.lib import spans


def read(context):
    win = context["window"]
    waits = spans.durations_ms(win, "data_wait")
    if not waits or win.seconds <= 0:
        return None
    return 100.0 * sum(waits) / 1e3 / win.seconds
