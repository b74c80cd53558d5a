"""Share of the HBM roofline reached by the paged-attention decode
kernel: bytes of the KV pages it must walk in the traced stretch
(``serve.pages_attended`` over the stretch, times the architecture's
``page_bytes``, every layer's share of one page) over the kernel's
device time (its ops as the architecture names them,
``DECODE_KERNELS``), over peak HBM bandwidth.  Bytes-bound: one query
row per page read."""

import devtrace
import harness


def read(ctx):
    c = ctx["config"]
    arch = harness.arch(c["model_type"])
    tr = ctx["window"].traced
    pages = tr.get("pages1", 0) - tr.get("pages0", 0)
    ns = devtrace.time_matching(ctx["trace"], arch.DECODE_KERNELS)
    nbytes = arch.page_bytes(c, c["service"]["page_size"]) * pages
    return harness.bytes_share(nbytes, ns / 1e9,
                               ctx["peaks"]["hbm_bytes_per_s"])
