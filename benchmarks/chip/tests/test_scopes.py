"""Device time by JAX name scope: `trace.extract` keeps each device op's name
stack (the ``tf_op`` stat of its event metadata on the TPU), and
`Trace.scope_seconds` sums the time of the ops under a named scope.  Checked
on a small ``.xplane.pb`` written here, field by field."""

from __future__ import annotations

import json
import pathlib

import pytest

from chipbench import trace

DATA = pathlib.Path(__file__).with_name("data")


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _int(field: int, n: int) -> bytes:
    return _varint(field << 3) + _varint(n)


def _msg(field: int, *parts) -> bytes:
    body = b"".join(p.encode() if isinstance(p, str) else p for p in parts)
    return _varint(field << 3 | 2) + _varint(len(body)) + body


def _str(field: int, text: str) -> bytes:
    return _msg(field, text)


def xspace() -> bytes:
    """One TPU plane whose ops line runs, from 1000 ns on: a router fusion
    [0, 10) under ``moe/route``, an expert kernel [10, 40) under
    ``moe/experts`` (its name stack interned: a ``ref_value``), a ``while``
    [0, 50) with no name stack, a head fusion [40, 50) outside ``moe``; and a
    host plane with the window's annotation over [1000, 1050)."""
    stat_md = [(1, "tf_op"), (2, "hlo_category"),
               (3, "jit(f)/moe/experts/sfc_gemm/pallas_call:")]
    event_md = [
        (1, "%fusion.1 = f32[8,8] fusion()", _msg(5, _int(1, 1), _str(
            5, "jit(f)/while/body/moe/route/argmax:"))),
        (2, "%sfc_gemm.2 = bf16[8,8] custom-call()", _msg(5, _int(1, 1),
                                                          _int(7, 3))),
        (3, "%while.3 = (s32[]) while()", _msg(5, _int(1, 2), _str(5, "while"))),
        (4, "%fusion.4 = f32[8,8] fusion()", _msg(5, _int(1, 1), _str(
            5, "jit(f)/moement/head/dot_general:"))),
    ]
    events = [(1, 0, 10), (2, 10_000, 30), (3, 0, 50), (4, 40_000, 10)]
    device = _msg(
        1, _int(1, 1), _str(2, "/device:TPU:0"),
        _msg(3, _int(1, 1), _str(2, "XLA Ops"), _int(3, 1000), *[
            _msg(4, _int(1, mid), _int(2, off), _int(3, dur * 1000))
            for mid, off, dur in events]),
        *[_msg(4, _int(1, i), _msg(2, _int(1, i), _str(2, name), stats))
          for i, name, stats in event_md],
        *[_msg(5, _int(1, i), _msg(2, _int(1, i), _str(2, name)))
          for i, name in stat_md])
    host = _msg(
        1, _int(1, 2), _str(2, "/host:CPU"),
        _msg(3, _int(1, 1), _str(2, "python3"), _int(3, 1000),
             _msg(4, _int(1, 1), _int(2, 0), _int(3, 50_000))),
        _msg(4, _int(1, 1), _msg(2, _int(1, 1), _str(2, trace.WINDOW_SPAN))))
    return device + host


@pytest.fixture
def events(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(xspace())
    return trace.extract(str(path))


def test_extract_keeps_each_ops_name_stack(events):
    ops = events["devices"]["/device:TPU:0"]["ops"]
    assert [(e[0].split(" = ")[0], e[1], e[2], e[3]) for e in ops] == [
        ("%fusion.1", 1000.0, 10.0, "jit(f)/while/body/moe/route/argmax:"),
        ("%sfc_gemm.2", 1010.0, 30.0,
         "jit(f)/moe/experts/sfc_gemm/pallas_call:"),
        ("%while.3", 1000.0, 50.0, ""),  # a stat of another name
        ("%fusion.4", 1040.0, 10.0, "jit(f)/moement/head/dot_general:"),
    ]
    assert events["window"] == [1000.0, 1050.0]


@pytest.mark.parametrize("scope, ns", [
    ("moe", 40), ("moe/route", 10), ("route", 10), ("experts", 30),
    ("sfc_gemm", 30), ("jit(f)", 50), ("head", 10),
    ("mo", 0), ("while", 10), ("moe/experts/sfc_gemm", 30), ("absent", 0),
])
def test_scope_seconds(events, scope, ns):
    # whole segments only ("moe" is not "moement"); the while itself, with
    # no stack, is control flow and never counts
    assert trace.Trace(events).scope_seconds(scope) == pytest.approx(ns * 1e-9)


def test_ops_out_of_step_with_their_metadata_are_refused(tmp_path, monkeypatch):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(xspace())
    real = trace.name_stacks
    monkeypatch.setattr(trace, "name_stacks", lambda p: {
        k: v[1:] + v[:1] for k, v in real(p).items()})
    with pytest.raises(ValueError, match="differ"):
        trace.extract(str(path))


@pytest.mark.parametrize("path", sorted(DATA.glob("*.events.json")),
                         ids=lambda p: p.name)
def test_events_without_name_stacks_read_no_scope(path):
    t = trace.Trace(json.loads(path.read_text()))
    assert t.busy_s() > 0
    assert t.scope_seconds("while") == 0.0
