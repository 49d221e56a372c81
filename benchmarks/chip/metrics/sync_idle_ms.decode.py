"""sync_idle_ms.decode: device-idle time, in ms per decode step, while the
engine brings the step's tokens to the host: inside the ``serving/token_sync``
spans of each ``serving/decode`` span (the argmax and one read per row)."""

from chipbench.spans import idle_ms_per_decode


def read(ctx):
    return idle_ms_per_decode(ctx["trace"], "serving/token_sync")
