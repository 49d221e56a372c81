"""idle_share.decode: share of the traced window, in %, in which the chip ran
no operation, in the decode cells."""


def read(ctx):
    return 100.0 * ctx["trace"].idle_share()
