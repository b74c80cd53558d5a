"""Share of the bf16 peak reached by the engine's QR megakernel: tile
operations of the whole plans in the traced stretch
(``counts.qr_plan_flops``) over the kernel's device time.  The tiles are
float32 at HIGHEST precision (six bf16 passes per product) and 64 x 64,
so this share cannot approach the bf16 peak; it is the published peak
all the same, so that a faster path reads higher."""

import devtrace
import harness

# The megakernel has no name of its own in the trace: it is the QR
# path's only Pallas call, so its ops are the Mosaic custom calls.
KERNELS = ('custom_call_target="tpu_custom_call"',)


def read(ctx):
    ns = devtrace.time_matching(ctx["trace"], KERNELS)
    return harness.flops_share(ctx["traced_plans"] * ctx["plan_flops"],
                               ns / 1e9, ctx["peaks"]["bf16_flops_per_s"])
