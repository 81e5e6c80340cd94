"""Time the program spent tracing, lowering or compiling inside the
window, in ms: what its ``compile`` spans cover, thread by thread, nested
events counted once. 0.0 in a sound run. Nothing where the log's ``step``
spans carry no attributes: such a program has no compile listener, and no
span then does not mean no compile."""

from . import span_attrs


def read(context):
    if not span_attrs.carrying(context, "step", "cpu_s"):
        return None
    by_thread = {}
    for s in context["window"].spans:
        if s["name"] == "compile":
            by_thread.setdefault(s["tid"], []).append(
                (s["ts"], s["ts"] + s["dur_s"]))
    covered = 0.0
    for intervals in by_thread.values():
        end = float("-inf")
        for lo, hi in sorted(intervals):
            covered += max(hi - max(lo, end), 0.0)
            end = max(end, hi)
    return 1e3 * covered
