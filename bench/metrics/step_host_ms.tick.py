"""Host milliseconds per service tick that the device may wait on: the
time inside the program's ``serve.step`` spans and outside their
``serve.sync`` children (the device-to-host reads, in which the host
waits on the device), over the traced run's window and drain, divided
by its ticks.  A program without these spans gives nothing."""

import devtrace


def _overlap(a, b):
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def read(ctx):
    w = ctx["window"]
    steps = devtrace.union((s.t0, s.t1) for s in w.spans
                           if s.name == "serve.step")
    if not steps or not w.ticks:
        return None
    syncs = devtrace.union((s.t0, s.t1) for s in w.spans
                           if s.name == "serve.sync")
    inside = sum(e - s for s, e in steps)
    return 1e3 * (inside - _overlap(steps, syncs)) / w.ticks
