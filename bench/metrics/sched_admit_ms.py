"""Mean host time per scheduling step of the program's own
``sched.admit`` span, in ms, over the steps of the traced window, read
from the profiler's host plane."""


def read(ctx):
    t = ctx.get("trace")
    spans = (t or {}).get("spans", {}).get("sched.admit") or []
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
