"""Prefill's share of the chip's bf16 peak over the whole window (host
clock): operations of every prefill that yielded a first token inside the
window (the architecture's ``prefill_flops``, ``archs/<model_type>.py``)
over window seconds times peak."""

import harness


def read(ctx):
    return harness.flops_share(ctx["measure"]["prefill_flops"],
                               ctx["window"].seconds,
                               ctx["peaks"]["bf16_flops_per_s"])
