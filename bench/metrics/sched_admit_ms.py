"""Mean host time per scheduling step of the program's own
``sched.admit`` telemetry span, over the steps of the traced window."""


def read(ctx):
    t = ctx.get("trace")
    spans = (t or {}).get("spans", {}).get("sched.admit") or []
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
