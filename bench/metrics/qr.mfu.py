"""The whole QR plan's share of the bf16 peak (host clock): tile
operations of one plan (``counts.qr_plan_flops``, about 4/3 n^3) over
the mean wall time of this run's plans."""

import harness


def read(ctx):
    if not ctx["plan_ms"]:
        return None
    return harness.flops_share(ctx["plan_flops"], ctx["plan_ms"] / 1e3,
                               ctx["peaks"]["bf16_flops_per_s"])
