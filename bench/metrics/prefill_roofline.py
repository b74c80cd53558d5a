"""Share of the bf16 peak reached by the prefill programs while they run:
operations of the prefills that yielded a first token inside the traced
stretch (the architecture's ``prefill_flops``) over the device time of
the ``prefill_entry`` modules there.
Compute-bound at these lengths (operations per weight byte = prompt
tokens in the batch)."""

import devtrace
import harness

MODULES = ("prefill_entry",)


def read(ctx):
    ns = devtrace.time_matching(ctx["trace"], MODULES, devtrace.MODULES)
    return harness.flops_share(ctx["measure"]["trace_prefill_flops"],
                               ns / 1e9, ctx["peaks"]["bf16_flops_per_s"])
