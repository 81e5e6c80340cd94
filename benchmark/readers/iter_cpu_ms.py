"""Mean CPU time of the loop thread per iteration (``cpu_s`` of the
timed ``iter`` spans), in ms."""


def read(context):
    cpu = [s["attrs"]["cpu_s"] for s in context["window"].iters
           if "cpu_s" in s.get("attrs", ())]
    return 1e3 * sum(cpu) / len(cpu) if cpu else None
