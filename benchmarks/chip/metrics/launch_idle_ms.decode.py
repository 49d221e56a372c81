"""launch_idle_ms.decode: device-idle time, in ms per decode step, while the
engine dispatches the decode program: inside the ``serving/launch`` spans of
each ``serving/decode`` span."""

from chipbench.spans import idle_ms_per_decode


def read(ctx):
    return idle_ms_per_decode(ctx["trace"], "serving/launch")
