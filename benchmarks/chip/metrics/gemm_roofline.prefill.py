"""gemm_roofline.prefill: the least time the traced rounds' projection GEMMs can
take on this chip (each at the larger of FLOPs over peak and bytes over
bandwidth) over the device time of the SFC GEMM kernels and of the copies
that slice their weights out of the stacked layers ahead of them (XLA stages
some into fast memory, so those copies do the kernels' reads from HBM), in
%."""

from chipbench.trace import op_matcher

SFC_GEMM = op_matcher(["sfc_gemm"])
STAGING = op_matcher(["dynamic-slice"])


def read(ctx):
    kernel_s = ctx["trace"].kernel_seconds(SFC_GEMM, staging=STAGING)
    return 100.0 * ctx["work"]["gemm_least_s"] / kernel_s if kernel_s else None
