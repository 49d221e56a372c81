"""The program's own host spans in a traced window, and the device-idle
time under them.

`ServingEngine.run` wraps each decode iteration in a ``serving/decode`` span
with two children: ``serving/launch`` (the dispatch of the decode program)
and ``serving/token_sync`` (the argmax and the per-row reads of the tokens
to the host).  `repro.obs.span` forwards each span to
`jax.profiler.TraceAnnotation`, so they are host events of the trace, on
the device's clock; their attributes are not part of the name.
"""

from __future__ import annotations

import bisect
from typing import List, Optional

from chipbench.trace import Interval, Trace, subtract, total, union

DECODE = "serving/decode"


def host_spans(trace: Trace, name: str) -> List[Interval]:
    """Sorted intervals of the host events named ``name`` that lie wholly
    inside the window."""
    return sorted((s, s + d) for n, s, d in trace.events.get("host") or []
                  if n == name and s >= trace.lo and s + d <= trace.hi)


def idle_ms_per_decode(trace: Trace, child: str) -> Optional[float]:
    """Device-idle time on the first device, in ms per decode step, inside
    the ``child`` events that lie inside a ``serving/decode`` event.  None
    where the window has no decode span or no such child (a program that
    does not emit them)."""
    decodes = host_spans(trace, DECODE)
    starts = [s for s, _ in decodes]
    kids = []
    for s, e in host_spans(trace, child):
        j = bisect.bisect_right(starts, s) - 1
        if j >= 0 and e <= decodes[j][1]:
            kids.append((s, e))
    if not decodes or not kids:
        return None
    idle = total(subtract(union(kids), trace.busy(trace.devices[0])))
    return idle / len(decodes) * 1e-6
