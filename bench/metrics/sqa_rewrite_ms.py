"""Mean host time of the program's ``engine.set_tau`` span (one
`SweepEngine.set_slot_tau` call, which writes every due slot's tau row
of a scheduling step), in ms, over the calls of the traced window, read
from the profiler's host plane."""


def read(ctx):
    t = ctx.get("trace")
    spans = (t or {}).get("spans", {}).get("engine.set_tau") or []
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
