"""``init_s``: see layer_metrics/init_s.json."""


def read(context):
    return context["host"].get("init_s")
