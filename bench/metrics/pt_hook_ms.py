"""Mean host time of the program's ``pt.hook`` span (`PTJob.on_segment`,
a ladder's swap phase), in ms, over the calls of the traced window, read
from the profiler's host plane."""


def read(ctx):
    t = ctx.get("trace")
    spans = (t or {}).get("spans", {}).get("pt.hook") or []
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
