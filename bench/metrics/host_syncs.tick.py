"""Device-to-host reads per service tick: the program's ``serve.sync``
spans (one around each read the tick path makes) over the traced run's
window and drain, divided by its ticks.  A program without these spans
gives nothing."""


def read(ctx):
    w = ctx["window"]
    n = sum(1 for s in w.spans if s.name == "serve.sync")
    if not n or not w.ticks:
        return None
    return n / w.ticks
