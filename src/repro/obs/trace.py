"""Span tracing over the hot control-plane paths.

``with span("serving/prefill", request_id=...)`` times a region, records
its duration into the ``span.<name>_us`` histogram of the process metrics
registry, and — when a JAX profiler session is active — forwards the name
to ``jax.profiler.TraceAnnotation`` so the same region lands in real TPU
traces next to the kernels it launched.

Span taxonomy (the names the stack emits; see README "Observability"):

    tune/tune_gemm       knob resolution sweep for one (op, shape bucket)
    tune/calibrate       platform-constants micro-sweep + fit
    ladder/run           one `run_with_fallback` rung walk (label-free;
                         the namespace rides in `ladder.served` counters)
    abft/verify          one checksum comparison
    serving/admission    request batching + overdue shedding
    serving/prefill      one batched prefill, until its first tokens are
                         on the host
    serving/decode       one decode iteration: deadline check, launch,
                         token sync, then bookkeeping (its self time)
    serving/launch       the dispatch of a prefill or decode program
                         (child of serving/prefill or serving/decode)
    serving/token_sync   the greedy-token program and one device->host
                         transfer of the step's tokens
                         (child of serving/prefill or serving/decode)
    serving/retire       end-of-batch request bookkeeping
    train/batch          host-side batch materialization
    train/step           one train_step call (jit dispatch + wait)
    train/checkpoint     checkpoint save at a step boundary

Spans are metrics, not a causal trace: attributes are forwarded to the
profiler annotation only (they would explode label cardinality in the
registry).  When observability is disabled the context manager yields
immediately — no clock reads, no annotation.
"""

from __future__ import annotations

import contextlib
import time
from typing import Iterator

from repro.obs import metrics

__all__ = ["span", "SPAN_NAMES"]

# the documented taxonomy — tests gate that instrumented paths stay on it
SPAN_NAMES = (
    "tune/tune_gemm",
    "tune/calibrate",
    "ladder/run",
    "abft/verify",
    "serving/admission",
    "serving/prefill",
    "serving/decode",
    "serving/launch",
    "serving/token_sync",
    "serving/retire",
    "train/batch",
    "train/step",
    "train/checkpoint",
)

_TRACE_ANNOTATION = None  # resolved lazily; False = unavailable


def _annotation_cls():
    global _TRACE_ANNOTATION
    if _TRACE_ANNOTATION is None:
        try:
            from jax.profiler import TraceAnnotation

            _TRACE_ANNOTATION = TraceAnnotation
        except Exception:  # pragma: no cover - jax without profiler
            _TRACE_ANNOTATION = False
    return _TRACE_ANNOTATION


@contextlib.contextmanager
def span(name: str, **attrs) -> Iterator[None]:
    """Time a region into ``span.<name>_us`` and mirror it into an active
    JAX profile.  Exceptions propagate; the duration is still recorded
    (a failing prefill is exactly the sample you want in the tail)."""
    if not metrics.enabled():
        yield
        return
    cls = _annotation_cls()
    ann = None
    if cls:
        try:
            # TraceAnnotation is ~free outside an active profiler session
            # and stamps the TraceMe row inside one; attrs ride along as
            # TraceMe metadata
            ann = cls(name, **attrs)
            ann.__enter__()
        except Exception:
            ann = None
    t0 = time.perf_counter()
    try:
        yield
    finally:
        dt_us = (time.perf_counter() - t0) * 1e6
        if ann is not None:
            try:
                ann.__exit__(None, None, None)
            except Exception:
                pass
        metrics.observe(f"span.{name}_us", dt_us)
