"""Mean time the loop thread spent inside the dispatch call without being
on the CPU (``dur_s - cpu_s`` of the ``step`` spans), in ms. A mean, not a
median: ``time.thread_time`` may tick in 10 ms steps."""

from . import span_attrs


def read(context):
    calls = span_attrs.carrying(context, "step", "cpu_s")
    if not calls:
        return None
    blocked = sum(s["dur_s"] - s["attrs"]["cpu_s"] for s in calls)
    return 1e3 * blocked / len(calls)
