"""What the readers of span attributes share. A span of the program's obs
log may carry an ``attrs`` object (counts and states measured where the
work happens); a log written by a program without them has none, and every
reader here then finds nothing to read."""


def carrying(context, name: str, key: str) -> list:
    """The window's spans called ``name`` that carry attribute ``key``."""
    return [s for s in context["window"].spans
            if s["name"] == name and key in s.get("attrs", ())]


def values(context, name: str, key: str) -> list:
    return [s["attrs"][key] for s in carrying(context, name, key)]


def share_pct(context, name: str, key: str):
    """Share of the spans called ``name`` whose ``key`` is true, in %."""
    flags = values(context, name, key)
    return 100.0 * sum(map(bool, flags)) / len(flags) if flags else None


def per_row_us(context, key: str):
    """Sum of ``key`` (seconds) over the rows made, over the ``collect``
    spans that carry both, in microseconds a row."""
    spans = [s for s in carrying(context, "collect", key)
             if s["attrs"].get("rows")]
    rows = sum(s["attrs"]["rows"] for s in spans)
    if not rows:
        return None
    return 1e6 * sum(s["attrs"][key] for s in spans) / rows
