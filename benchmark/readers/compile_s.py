"""``compile_s``: see layer_metrics/compile_s.json."""


def read(context):
    return context["host"].get("compile_s")
