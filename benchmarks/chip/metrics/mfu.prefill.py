"""mfu.prefill: the model FLOPs of the traced rounds (every projection, the
attention over each token's causal context, the LM head where the served
result needs it) over the traced window, as a share, in %, of the chips'
bf16 peak."""


def read(ctx):
    t = ctx["trace"]
    peak = ctx["peaks"]["bf16_flops"] * len(t.devices)
    return 100.0 * ctx["work"]["flops"] / t.window_s / peak
