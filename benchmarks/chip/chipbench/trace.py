"""Profiler trace: capture, extraction to plain events, and reduction.

A traced window is written by `jax.profiler` as an ``.xplane.pb``.  It is
read with `jax.profiler.ProfileData` and cut down to a plain dict, the
*events*::

    {"devices": {"/device:TPU:0": {"ops": [[name, start_ns, dur_ns], ...],
                                   "modules": [[name, start_ns, dur_ns], ...]}},
     "host": [[name, start_ns, dur_ns], ...],
     "window": [start_ns, end_ns]}

``ops`` are the device's HLO operations (kernels, fusions, collectives),
``modules`` the executions of whole compiled programs, ``host`` the
annotations and calls of the Python thread that drives the chip, and
``window`` the span of the benchmark's ``bench/window`` annotation.  Every
metric below is computed from these events alone, so a recorded trace
(``tests/data``) checks the arithmetic on the CPU.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Sequence, Tuple

WINDOW_SPAN = "bench/window"
# device lines, by the names the TPU profiler gives them
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# control flow: an op of these spans the ops of its body on the same line
CONTROL = ("while", "conditional", "call")
# collectives as XLA names their HLO operations
COLLECTIVE_WORDS = ("reduce-scatter", "all-reduce", "all-gather",
                    "collective-permute", "all-to-all")

Interval = Tuple[float, float]


# ---------------------------------------------------------------------------
# extraction
# ---------------------------------------------------------------------------


def xplane_path(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def extract(path: str) -> dict:
    """The plain events of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, dict] = {}
    host: List[list] = []
    planes: Dict[str, List[str]] = {}
    for plane in pd.planes:
        planes[plane.name] = [line.name for line in plane.lines]
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                continue
            devices[plane.name] = {
                "ops": _events(lines[OPS_LINE].events),
                "modules": (_events(lines[MODULES_LINE].events)
                            if MODULES_LINE in lines else []),
            }
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                evs = _events(line.events)
                if any(e[0] == WINDOW_SPAN for e in evs):
                    host = evs
    window = next(([e[1], e[1] + e[2]] for e in host if e[0] == WINDOW_SPAN),
                  None)
    return {"devices": devices, "host": host, "window": window,
            "planes": planes}


def _events(events: Iterable) -> List[list]:
    return [[e.name, float(e.start_ns), float(e.duration_ns)] for e in events]


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint union of half-open intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the disjoint sorted intervals ``a`` that ``b`` (also disjoint
    and sorted) does not cover."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------


def op_name(text: str) -> str:
    """``fusion.12`` of an op event named by its HLO text, ``%fusion.12 =
    bf16[...] fusion(...)`` (a plain name stays as it is)."""
    return text.split(" = ", 1)[0].lstrip("%")


def base_name(name: str) -> str:
    return re.sub(r"\.\d+$", "", op_name(name))  # fusion.12 -> fusion


def operand_names(text: str) -> List[str]:
    """The ops an op event's HLO text reads: its ``%name`` operands."""
    return re.findall(r"%([\w.\-]+)", text.split(" = ", 1)[1]) if " = " in text else []


class Op(NamedTuple):
    text: str
    base: str
    start: float
    end: float
    self_ns: float  # a control-flow op's duration less its body's ops
    leaf: bool      # not a control-flow op


class Trace:
    """Metrics of one traced window, from its plain events.

    The device's ops line nests: a ``while`` over a model's layers spans the
    ops of its body.  Busy time is the union of all ops; a control-flow op's
    own time is what its body's ops leave of it; overlap is judged between
    the other (leaf) ops only."""

    def __init__(self, events: dict):
        self.events = events
        self.devices = sorted(events["devices"])
        if not self.devices:
            raise ValueError("the trace holds no device with an ops line")
        win = events.get("window")
        if win is None:  # no host annotation: the span of the device ops
            spans = [(s, s + d) for dev in self.devices
                     for _, s, d in events["devices"][dev]["ops"]]
            win = [min(s for s, _ in spans), max(e for _, e in spans)]
        self.lo, self.hi = float(win[0]), float(win[1])
        self._ops = {d: self._nest(events["devices"][d]["ops"])
                     for d in self.devices}

    def _nest(self, raw) -> List[Op]:
        ops = [(t, base_name(t), max(s, self.lo), min(s + d, self.hi))
               for t, s, d in raw if s + d > self.lo and s < self.hi]
        inner = union((s, e) for _, b, s, e in ops if b not in CONTROL)
        return [Op(t, b, s, e, (e - s) - (total(clip(inner, s, e))
                                          if b in CONTROL else 0.0),
                   b not in CONTROL)
                for t, b, s, e in ops]

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def busy(self, device: str, match=None, leaves: bool = False) -> List[Interval]:
        return union((o.start, o.end) for o in self._ops[device]
                     if (match is None or match(o.base))
                     and (o.leaf or not leaves))

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the devices."""
        return sum(total(self.busy(d)) for d in self.devices) * 1e-9 / len(
            self.devices)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def kernel_seconds(self, match, staging=None) -> float:
        """Device time of the ops ``match`` accepts, averaged over the
        devices.  With ``staging``, also that of the ops their operands
        come from that ``staging`` accepts: XLA may copy a kernel's operand
        into fast memory ahead of it (a weight sliced out of the stacked
        layers), and that copy does the kernel's reads from HBM."""
        out = 0.0
        for d in self.devices:
            kernels = [o for o in self._ops[d] if match(o.base)]
            out += sum(o.end - o.start for o in kernels)
            if staging:
                feeds = {n for o in kernels for n in operand_names(o.text)}
                out += sum(o.end - o.start for o in self._ops[d]
                           if staging(o.base) and op_name(o.text) in feeds)
        return out * 1e-9 / len(self.devices)

    def exposed_seconds(self, match) -> float:
        """Time of the leaf ops ``match`` accepts with no other leaf op
        running beside them, averaged over the devices."""
        out = 0.0
        for d in self.devices:
            mine = self.busy(d, match, leaves=True)
            others = self.busy(d, lambda n: not match(n), leaves=True)
            out += total(subtract(mine, others))
        return out * 1e-9 / len(self.devices)

    def module_gaps(self, match, apart=None) -> List[float]:
        """Device-idle nanoseconds between consecutive executions of the
        programs ``match`` accepts, on the first device.  A pair with a
        program that ``apart`` accepts between them is not consecutive."""
        dev = self.devices[0]
        mods = sorted((s, s + d, bool(match(name))) for name, s, d in
                      self.events["devices"][dev]["modules"]
                      if s >= self.lo and s + d <= self.hi
                      and (match(name) or (apart and apart(name))))
        busy = self.busy(dev)
        gaps = []
        for (_, end, m0), (start, _, m1) in zip(mods, mods[1:]):
            if m0 and m1 and start > end:
                gaps.append(total(subtract([(end, start)], busy)))
        return gaps

    def top_ops(self, n: int = 10) -> List[list]:
        """The ``n`` op names (numeric suffixes dropped) with the most self
        time, averaged over the devices: [[name, seconds], ...]."""
        acc: Dict[str, float] = {}
        for d in self.devices:
            for o in self._ops[d]:
                acc[o.base] = acc.get(o.base, 0.0) + o.self_ns * 1e-9 / len(
                    self.devices)
        return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])][:n]

    def idle_gaps(self, n: int = 10) -> List[list]:
        """The ``n`` longest idle gaps of the first device inside the
        window, each named by the innermost host event running at its
        midpoint: [[host name, seconds], ...]."""
        dev = self.devices[0]
        gaps = subtract([(self.lo, self.hi)], self.busy(dev))
        gaps.sort(key=lambda iv: iv[0] - iv[1])
        host = self.events.get("host") or []
        out = []
        for s, e in gaps[:n]:
            mid = (s + e) / 2
            covering = [(d, name) for name, hs, d in host
                        if hs <= mid <= hs + d and name != WINDOW_SPAN]
            name = min(covering)[1] if covering else "(no host event)"
            out.append([name, (e - s) * 1e-9])
        return out

    def summary(self) -> dict:
        """Per op name: count, summed duration and self time (s), on the
        first device; per program: executions."""
        dev = self.devices[0]
        ops: Dict[str, list] = {}
        for o in self._ops[dev]:
            c = ops.setdefault(o.base, [0, 0.0, 0.0])
            c[0] += 1
            c[1] += (o.end - o.start) * 1e-9
            c[2] += o.self_ns * 1e-9
        mods: Dict[str, int] = {}
        for name, s, d in self.events["devices"][dev]["modules"]:
            if s >= self.lo and s + d <= self.hi:
                key = name.split("(")[0]
                mods[key] = mods.get(key, 0) + 1
        return {"ops": ops, "modules": mods, "busy_s": self.busy_s(),
                "window_s": self.window_s}


def trimmed(events: dict, max_ops: int = 400) -> dict:
    """A small copy of ``events`` (the first ``max_ops`` ops per device, the
    window cut where the last of them that is no control flow ends, and the
    host events that start before), for recording a test trace."""
    devs = {}
    hi = None
    for name, dev in events["devices"].items():
        ops = sorted(dev["ops"], key=lambda e: e[1])[:max_ops]
        devs[name] = {"ops": ops, "modules": [
            m for m in dev["modules"] if ops and m[1] <= ops[-1][1]]}
        end = max(s + d for t, s, d in ops if base_name(t) not in CONTROL)
        hi = end if hi is None else max(hi, end)
    lo = events["window"][0] if events.get("window") else None
    host = [h for h in events.get("host", []) if hi is None or h[1] <= hi]
    return {"devices": devs, "host": host,
            "window": [lo, hi] if lo is not None else None,
            "planes": events.get("planes", {})}


def _folded(name: str) -> str:
    return name.lower().replace("_", "-")  # XLA writes reduce_scatter too


def op_matcher(words: Sequence[str]):
    """Accepts a name that holds any of ``words`` (case, ``_`` and ``-``
    alike)."""
    words = tuple(_folded(w) for w in words)
    return lambda name: any(w in _folded(name) for w in words)


is_collective = op_matcher(COLLECTIVE_WORDS)

