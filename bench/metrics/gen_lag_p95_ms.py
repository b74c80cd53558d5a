"""95th percentile of how late the load generator submitted the window's
requests against their due times (host clock).  A late generator offers
less load than the mix says: read it beside ``ttft_p50_ms``."""

import harness


def read(ctx):
    lags = ctx["window"].lags_ms
    return harness.quantile(lags, 0.95) if lags else None
