"""Host time per scheduling step inside the program's ``engine.set_betas``
span (`SweepEngine.set_slot_betas`, which writes slots' betas), in ms:
the sum of those spans over the number of ``sched.step`` spans of the
traced window, read from the profiler's host plane."""


def read(ctx):
    spans = ((ctx.get("trace") or {}).get("spans") or {})
    steps = spans.get("sched.step") or []
    if not steps:
        return None
    return 1e3 * sum(spans.get("engine.set_betas") or []) / len(steps)
