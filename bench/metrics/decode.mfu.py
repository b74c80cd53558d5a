"""Decode's share of the chip's bf16 peak over the whole window (host
clock): operations of every decoded token stamped inside the window
(the architecture's ``decode_flops`` at its cache position,
``archs/<model_type>.py``) over window seconds times peak."""

import harness


def read(ctx):
    return harness.flops_share(ctx["measure"]["decode_flops"],
                               ctx["window"].seconds,
                               ctx["peaks"]["bf16_flops_per_s"])
