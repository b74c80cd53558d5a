"""Host milliseconds per service tick in the scheduler: the union of the
``repro.obs`` spans ``core.prepare``, ``plan.lower``, ``plan.color_phases``
and ``engine.lower_tables`` (``engine.encode`` nests inside it) over the
traced run's window and drain, divided by its ticks."""

import devtrace

NAMES = ("core.prepare", "plan.lower", "plan.color_phases",
         "engine.lower_tables")


def read(ctx):
    w = ctx["window"]
    spans = [(s.t0, s.t1) for s in w.spans if s.name in NAMES]
    if not spans or not w.ticks:
        return None
    total = sum(e - s for s, e in devtrace.union(spans))
    return 1e3 * total / w.ticks
