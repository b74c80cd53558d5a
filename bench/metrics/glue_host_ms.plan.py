"""Host milliseconds per QR plan spent on tile glue around the scheduler
and the megakernel: the union of the program's spans ``qr.split_tiles``,
``qr.build_graph``, ``engine.buffers``, ``engine.writeback`` and
``qr.assemble_r`` over the traced run's window, divided by the plans
completed.  A program without these spans gives nothing."""

import devtrace

NAMES = ("qr.split_tiles", "qr.build_graph", "engine.buffers",
         "engine.writeback", "qr.assemble_r")


def read(ctx):
    w = ctx["window"]
    spans = [(s.t0, s.t1) for s in w["spans"] if s.name in NAMES]
    if not spans or not w["plans"]:
        return None
    total = sum(e - s for s, e in devtrace.union(spans))
    return 1e3 * total / w["plans"]
