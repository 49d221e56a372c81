"""decode_gap_ms.decode: mean device-idle time, in ms, between two consecutive
executions of the engine's decode program (a prefill between them breaks the
pair).  What the host does between decode steps shows here."""

from chipbench.trace import op_matcher

DECODE = op_matcher(["decode_impl"])
PREFILL = op_matcher(["prefill_impl"])


def read(ctx):
    gaps = ctx["trace"].module_gaps(DECODE, apart=PREFILL)
    return sum(gaps) / len(gaps) * 1e-6 if gaps else None
