"""gemm_roofline.dist: the least time of each chip's local GEMM of the traced
calls (the larger of FLOPs over peak and bytes over bandwidth) over the device
time of the SFC GEMM kernels on that chip (and of any slice copy staging
their operands), averaged over the chips, in %."""

from chipbench.trace import op_matcher

SFC_GEMM = op_matcher(["sfc_gemm"])
STAGING = op_matcher(["dynamic-slice"])


def read(ctx):
    kernel_s = ctx["trace"].kernel_seconds(SFC_GEMM, staging=STAGING)
    return 100.0 * ctx["work"]["gemm_least_s"] / kernel_s if kernel_s else None
