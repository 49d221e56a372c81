"""mfu.dist: 2*M*N*K of every traced call over the traced window, as a share,
in %, of the bf16 peak of all the mesh's chips."""


def read(ctx):
    t = ctx["trace"]
    peak = ctx["peaks"]["bf16_flops"] * ctx["work"]["n_devices"]
    return 100.0 * ctx["work"]["flops"] / t.window_s / peak
