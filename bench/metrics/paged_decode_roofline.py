"""Share of the HBM roofline reached by the paged-attention decode
kernel: bytes of the KV pages it must walk in the traced stretch
(``serve.pages_attended`` over the stretch, times every layer's page
bytes, ``counts.paged_decode_bytes``) over the kernel's device time,
over peak HBM bandwidth.  Bytes-bound: one query row per page read."""

import counts
import devtrace
import harness

# The kernel has no name of its own in the trace: it is the serving
# path's only Pallas call, so its ops are the Mosaic custom calls.
KERNELS = ('custom_call_target="tpu_custom_call"',)


def read(ctx):
    tr = ctx["window"].traced
    pages = tr.get("pages1", 0) - tr.get("pages0", 0)
    ns = devtrace.time_matching(ctx["trace"], KERNELS)
    nbytes = counts.paged_decode_bytes(
        ctx["config"], ctx["config"]["service"]["page_size"], pages)
    return harness.bytes_share(nbytes, ns / 1e9,
                               ctx["peaks"]["hbm_bytes_per_s"])
