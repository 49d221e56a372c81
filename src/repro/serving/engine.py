"""Serving engine: batched prefill + decode with continuous batching.

The paper's LLM case study (SSIV-D) accelerates the compute-heavy *prefill*
with SFC-CA GEMM as the backend; here the analogous switch is
``gemm_backend``:

  "xla"          jnp.dot path (dry-runs / TPU XLA)
  "sfc_pallas"   every prefill projection GEMM routed through the Pallas
                 SFC-CA kernel (interpret on CPU, Mosaic on TPU) via the
                 monkey-patchable hook in `repro.serving.backend`
  "sfc_reference" Listing-1 reference algorithm

`benchmarks/llm_prefill.py` reproduces the Fig.-10 comparison with these
backends on a small model.

The `ServingEngine` keeps a fixed set of decode slots; finished sequences
retire and waiting requests are prefilled into their slots (continuous
batching at step granularity).
"""

from __future__ import annotations

import dataclasses
import queue
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import namespaces as ns
from repro.models.registry import build_model
from repro.obs import metrics as obs_metrics
from repro.obs.trace import span
from repro.serving import backend as backend_lib


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray  # (S,) int32
    max_new_tokens: int = 16
    # per-request latency budget, seconds from submission; None = no budget.
    # Overrun waiting requests are shed before prefill; overrun live decodes
    # retire at the next step boundary.  Either way status = "timed_out".
    deadline_s: Optional[float] = None
    # filled by the engine:
    status: str = "pending"  # pending | completed | timed_out
    output: Optional[List[int]] = None
    submitted_at: float = 0.0
    first_token_at: float = 0.0
    done_at: float = 0.0

    def past_deadline(self, now: float) -> bool:
        return (
            self.deadline_s is not None
            and now - self.submitted_at > self.deadline_s
        )


class ServingEngine:
    """Single-host batched serving for any registry model with a KV cache.

    Not a production HTTP server — the scheduling core that one would wrap:
    slot-based continuous batching, greedy sampling, per-request latency
    accounting."""

    def __init__(
        self,
        cfg: ArchConfig,
        params,
        *,
        max_batch: int = 8,
        max_seq: int = 256,
        gemm_backend: str = "xla",
        greedy: bool = True,
        verify_every: Optional[int] = None,
    ):
        self.cfg = cfg
        self.model = build_model(cfg)
        self.params = params
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.backend = gemm_backend
        # sampled ABFT verification: every Nth decode step runs a program
        # traced under abft="detect" — its kernel checksum lanes surface
        # silent corruption through the runtime SDC counters; a detection
        # quarantines the Pallas rungs and redoes the step on the healed
        # trace.  None/0 = off.
        self._verify_every = verify_every
        self._decode_steps = 0
        self._verified_steps = 0
        self._sdc_detections = 0

        self._jit()
        self._uid = 0

    def _jit(self) -> None:
        self._prefill = jax.jit(self._prefill_impl)
        self._decode = jax.jit(self._decode_impl)
        self._decode_verify = jax.jit(self._decode_verify_impl)

    # namespaces a compiled engine program may have routed through the
    # fallback ladder — what the runtime-failure path quarantines wholesale
    _LADDER_NAMESPACES = (
        ns.NS_GEMM, ns.NS_GLU, ns.NS_GROUPED, ns.NS_GROUPED_GLU,
        ns.NS_ATTN_FWD, ns.NS_ATTN_DECODE,
    )

    def _run_healed(self, which: str, *args):
        """Run a jitted program; on a *classified* failure quarantine the
        Pallas rungs of every namespace this engine routes (shape ``None``
        = whole rung), drop the jit caches so the next trace picks the
        fallback rungs, and retry once.  Unclassified errors propagate —
        self-healing covers platform breakage, not bugs.  Under
        ``REPRO_STRICT=1`` a classified failure that was not injected
        raises `StrictFallbackError` instead: a strict run never serves
        from a rung it did not ask for."""
        from repro.robust import (
            PALLAS_RUNGS,
            StrictFallbackError,
            classify_failure,
            get_registry,
            strict_mode,
        )
        from repro.robust.inject import InjectedFault

        try:
            return getattr(self, which)(self.params, *args)
        except Exception as exc:  # noqa: BLE001 — classified below
            kind = classify_failure(exc)
            if kind is None:
                raise
            injected = isinstance(exc, InjectedFault)
            if strict_mode() and not injected:
                raise StrictFallbackError(
                    f"REPRO_STRICT: engine program {which!r} failed "
                    f"({kind}); refusing to re-trace on fallback rungs: {exc}"
                ) from exc
            reg = get_registry()
            for namespace in self._LADDER_NAMESPACES:
                for rung in PALLAS_RUNGS:
                    reg.quarantine(
                        namespace, rung, None, kind,
                        injected=injected, error=exc,
                    )
            self._jit()  # drop caches: the retry re-traces on healthy rungs
            return getattr(self, which)(self.params, *args)

    def degradation_report(self) -> Dict[str, Any]:
        """Health-registry summary for the namespaces this engine serves,
        plus this engine's sampled-verification ledger (decode steps run,
        steps verified, runtime SDC detections that forced a redo)."""
        from repro.robust import degradation_report as _report

        rep = _report(namespaces=self._LADDER_NAMESPACES)
        rep["verify"] = {
            "verify_every": self._verify_every,
            "decode_steps": self._decode_steps,
            "verified_steps": self._verified_steps,
            "sdc_detections": self._sdc_detections,
        }
        return rep

    def _verified_decode(self, token, cache):
        """One decode step under abft="detect" with runtime-SDC handling.

        The verification program's checksum mismatches surface through
        `repro.robust.abft`'s runtime counters (debug callbacks — the
        jitted program cannot raise).  On a detection the Pallas rungs of
        every routed namespace are quarantined, the jit caches dropped,
        and the step *redone* on the healed trace — the corrupted logits
        and cache are discarded, so the KV state never absorbs the flip.
        """
        from repro.robust import abft as _abft

        self._verified_steps += 1
        before = _abft.runtime_sdc_total()
        out = self._run_healed("_decode_verify", token, cache)
        jax.effects_barrier()
        delta = _abft.runtime_sdc_total() - before
        if not delta:
            return out
        from repro.robust import PALLAS_RUNGS, get_registry

        self._sdc_detections += delta
        obs_metrics.inc("serving.sdc_redo", value=delta)
        reg = get_registry()
        for namespace in self._LADDER_NAMESPACES:
            for rung in PALLAS_RUNGS:
                reg.quarantine(namespace, rung, None, "sdc")
        self._jit()  # drop caches: the redo re-traces on healthy rungs
        return self._run_healed("_decode", token, cache)

    # ---------------- warmup / tuning ----------------

    def projection_gemm_shapes(
        self, prompt_len: int
    ) -> List[Tuple[str, int, int, int]]:
        """(op, M, N, K) of the dominant prefill projection GEMMs at this
        batch size: attention/ffn projections (per sequence, M=prompt_len)
        and the LM head.  ``op`` is "glu" for the gated up-projection (the
        fused dual-B kernel has its own knob landscape — two B panels share
        the A traversal) and "gemm" otherwise."""
        d, ff, v = self.cfg.d_model, self.cfg.d_ff, self.cfg.vocab
        shapes = [(ns.NS_GEMM, prompt_len, d, d)]
        if ff:
            up_op = (
                ns.NS_GLU if getattr(self.cfg, "gated_mlp", True)
                else ns.NS_GEMM
            )
            shapes += [
                (up_op, prompt_len, ff, d), (ns.NS_GEMM, prompt_len, d, ff),
            ]
        shapes.append((ns.NS_GEMM, self.max_batch, v, d))
        return shapes

    def tune_table(
        self,
        prompt_len: int,
        *,
        backward: bool = False,
        update: bool = False,
    ) -> List[Tuple[str, int, int, int]]:
        """The full (op, m, n, k) tune-namespace table warmup fills —
        one code path for every variant.

        Per forward projection shape: its own namespace ("gemm"/"glu");
        with ``backward`` the two backward buckets
        (`perf_model.backward_gemm_shapes`) in the namespaces the train-time
        VJP actually resolves — the *dual* NT/TN forms for GLU projections
        (the GLU backward streams two panels per traversal, its knob
        landscape differs); with ``update`` the grad-and-update flush
        namespaces ("tn_update"/"tn_update_dual") on the TN buckets."""
        from repro.core.perf_model import (
            attention_phase_shapes,
            backward_gemm_shapes,
        )

        entries: List[Tuple[str, int, int, int]] = []
        for (op, m, n, k) in self.projection_gemm_shapes(prompt_len):
            entries.append((op, m, n, k))
            if not (backward or update):
                continue
            bwd = backward_gemm_shapes(m, n, k)
            dual = op == ns.NS_GLU
            if backward:
                entries.append(
                    (ns.NS_NT_DUAL if dual else ns.NS_NT, *bwd[ns.NS_NT])
                )
                entries.append(
                    (ns.NS_TN_DUAL if dual else ns.NS_TN, *bwd[ns.NS_TN])
                )
            if update:
                entries.append((
                    ns.NS_TN_UPDATE_DUAL if dual else ns.NS_TN_UPDATE,
                    *bwd[ns.NS_TN],
                ))
        if getattr(self.cfg, "attn_impl", "") == "sfc":
            # the SFC attention kernels resolve their own namespaces:
            # prefill/training flash (and its backward, for fine-tuning
            # jobs that piggyback on warmup), plus the decode fan-out
            attn = attention_phase_shapes(
                prompt_len, prompt_len, self.cfg.head_dim_,
                n_heads=self.cfg.n_heads, cache_len=self.max_seq,
            )
            entries.append((ns.NS_ATTN_FWD, *attn[ns.NS_ATTN_FWD]))
            if backward:
                entries.append((ns.NS_ATTN_BWD, *attn[ns.NS_ATTN_BWD]))
            entries.append((ns.NS_ATTN_DECODE, *attn[ns.NS_ATTN_DECODE]))
        return entries

    def warmup(
        self,
        prompt_len: int = 32,
        *,
        tune: bool = False,
        tune_backward: bool = False,
        tune_update: bool = False,
        tune_strategy: str = "predict",
    ) -> Optional[Dict[str, Any]]:
        """Compile the prefill/decode programs for one prompt length before
        traffic arrives; with ``tune=True`` first run the knob tuner for
        this model's projection GEMM shapes — the fused GLU variant
        included — so the SFC backend traces with tuned winners (a second
        warmup for the same shape bucket is a pure cache hit — no
        re-measurement).

        Tuning is predict-then-confirm by default (tuner v2): the device is
        calibrated once (`repro.tune.calibrate` — a short micro-sweep,
        persisted per device kind), every candidate is ranked with the
        calibrated model, and only the top-2 per namespace are measured
        wall-clock.  ``tune_strategy="exhaustive"`` restores the v1
        measure-everything sweep for A/B.

        ``tune_backward=True`` additionally tunes the backward namespaces
        for the same projection shapes — ``op="nt"``/``op="tn"`` plus the
        ``"nt_dual"``/``"tn_dual"`` forms the GLU backward resolves at
        train time (`tune_table`) — and implies ``tune=True``.
        ``tune_update=True`` also fills the ``op="tn_update"`` /
        ``"tn_update_dual"`` namespaces the fused-optimizer flush resolves
        (and implies ``tune_backward``).  Serving itself never runs them,
        but the engine's warmup is the one place that already knows every
        projection shape, so fine-tuning jobs piggyback on it (see README
        "Training on the SFC backend").

        Returns a stats dict when tuning ran (``n_namespaces``,
        ``n_measured``, ``median_rel_err`` — predicted-vs-measured over
        the confirmation measurements — and the per-measurement
        ``report``), else None."""
        tune_backward = tune_backward or tune_update
        tune = tune or tune_backward
        stats: Optional[Dict[str, Any]] = None
        if tune and self.backend == "sfc_pallas":
            from repro.tune import calibrate, tune_gemm

            # fit the per-device platform constants once so the predictive
            # ranking below is calibrated, not datasheet guesswork (a
            # pure cache read after the first warmup on this device)
            calibrate()
            # key the cache by the dtype the projections will actually trace
            # with (activations follow param_dtype), or the lookup misses
            dtype = jnp.dtype(self.cfg.param_dtype)
            report: List[Dict[str, Any]] = []
            entries = self.tune_table(
                prompt_len, backward=tune_backward, update=tune_update
            )
            for (op, m, n, k) in entries:
                tune_gemm(m, n, k, dtype, op=op, strategy=tune_strategy,
                          report=report)
            errs = [
                abs(r["measured_s"] - r["predicted_s"]) / r["measured_s"]
                for r in report
                if r.get("predicted_s") and r["measured_s"] > 0
            ]
            stats = {
                "n_namespaces": len(entries),
                "n_measured": len(report),
                "median_rel_err": float(np.median(errs)) if errs else None,
                "report": report,
            }
        tokens = jnp.zeros((self.max_batch, prompt_len), jnp.int32)
        logits, cache = self._prefill(self.params, tokens)
        tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        jax.block_until_ready(self._decode(self.params, tok, cache))
        return stats

    # ---------------- jitted cores ----------------

    def _prefill_impl(self, params, tokens):
        with backend_lib.gemm_backend(self.backend):
            return self.model.prefill(params, tokens, cache_len=self.max_seq, remat="none")

    def _decode_impl(self, params, token, cache):
        with backend_lib.gemm_backend(self.backend):
            return self.model.decode_step(params, token, cache)

    def _decode_verify_impl(self, params, token, cache):
        from repro.robust.abft import abft_mode

        with backend_lib.gemm_backend(self.backend), abft_mode("detect"):
            return self.model.decode_step(params, token, cache)

    # ---------------- serving loop ----------------

    def submit_many(
        self,
        prompts: List[np.ndarray],
        max_new_tokens: int = 16,
        deadline_s: Optional[float] = None,
    ) -> List[Request]:
        reqs = []
        for p in prompts:
            self._uid += 1
            reqs.append(
                Request(
                    uid=self._uid,
                    prompt=np.asarray(p, np.int32),
                    max_new_tokens=max_new_tokens,
                    submitted_at=time.perf_counter(),
                    deadline_s=deadline_s,
                )
            )
        return reqs

    def run(self, requests: List[Request], eos_id: Optional[int] = None) -> List[Request]:
        """Process requests with slot-based continuous batching.

        Requests of equal prompt length are grouped into prefill batches (a
        production engine would pad/bucket; grouping keeps the example free
        of padding logic); decode proceeds for all live slots jointly and
        retired slots are immediately refilled from the queue.

        Per-request ``deadline_s`` budgets are enforced at two points:
        waiting requests past their deadline are *shed* before prefill
        (overload never spends compute on a request that already missed),
        and live decodes past their deadline retire at the next step
        boundary — both with ``status="timed_out"``."""
        waiting = list(requests)
        results: List[Request] = []
        obs_metrics.inc("serving.requests", value=len(requests))

        def shed_overdue() -> None:
            now = time.perf_counter()
            for r in [r for r in waiting if r.past_deadline(now)]:
                waiting.remove(r)
                r.status = "timed_out"
                r.done_at = now
                if r.output is None:
                    r.output = []
                self._record_retired(r)
                results.append(r)

        while waiting:
            with span("serving/admission"):
                shed_overdue()
                if not waiting:
                    break
                # group up to max_batch same-length prompts
                length = len(waiting[0].prompt)
                batch = [
                    r for r in waiting if len(r.prompt) == length
                ][: self.max_batch]
                for r in batch:
                    waiting.remove(r)

            tokens = jnp.asarray(np.stack([r.prompt for r in batch]))
            with span("serving/prefill", batch=len(batch)):
                logits, cache = self._run_healed("_prefill", tokens)
            now = time.perf_counter()
            next_tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
            # post-prefill deadline check: a long prefill can eat a whole
            # budget — retire those requests here (no first token emitted)
            # instead of letting them leak into the decode loop
            live = []
            for i, r in enumerate(batch):
                r.output = []
                if r.past_deadline(now):
                    r.status = "timed_out"
                    r.done_at = now
                else:
                    r.first_token_at = now
                    r.output.append(int(next_tok[i, 0]))
                    live.append(i)

            steps = max(r.max_new_tokens for r in batch) - 1
            for _ in range(steps):
                now = time.perf_counter()
                for i in list(live):
                    r = batch[i]
                    if r.past_deadline(now):
                        r.status = "timed_out"
                        r.done_at = now
                        live.remove(i)
                if not live:
                    break
                self._decode_steps += 1
                with span("serving/decode", step=self._decode_steps):
                    if self._verify_every and (
                        self._decode_steps % self._verify_every == 0
                    ):
                        logits, cache = self._verified_decode(next_tok, cache)
                    else:
                        logits, cache = self._run_healed(
                            "_decode", next_tok, cache
                        )
                next_tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
                still = []
                for i in live:
                    r = batch[i]
                    tok = int(next_tok[i, 0])
                    if len(r.output) < r.max_new_tokens:
                        r.output.append(tok)
                    finished = len(r.output) >= r.max_new_tokens or (
                        eos_id is not None and tok == eos_id
                    )
                    if finished:
                        r.status = "completed"
                        r.done_at = time.perf_counter()
                    else:
                        still.append(i)
                live = still
            with span("serving/retire"):
                now = time.perf_counter()
                for r in batch:
                    if not r.done_at:
                        r.status = "completed"
                        r.done_at = now
                    self._record_retired(r)
                results.extend(batch)
        return results

    # ---------------- metrics ----------------

    @staticmethod
    def _record_retired(r: Request) -> None:
        """Emit one request's lifecycle into the obs registry.  The same
        quantities `latency_report` summarises — TTFT, end-to-end latency,
        per-decoded-token latency — recorded as histograms so a fleet gets
        the p95 without holding Request objects."""
        obs_metrics.inc("serving." + (
            "timed_out" if r.status == "timed_out" else "completed"
        ))
        n_tok = len(r.output or [])
        if n_tok:
            obs_metrics.inc("serving.tokens", value=n_tok)
        if r.first_token_at > 0:
            obs_metrics.observe(
                "serving.ttft_us",
                (r.first_token_at - r.submitted_at) * 1e6,
            )
        else:
            obs_metrics.inc("serving.shed")
        if r.done_at > 0:
            obs_metrics.observe(
                "serving.e2e_us", (r.done_at - r.submitted_at) * 1e6
            )
        if r.first_token_at > 0 and n_tok > 1:
            obs_metrics.observe(
                "serving.token_us",
                (r.done_at - r.first_token_at) / (n_tok - 1) * 1e6,
            )

    @staticmethod
    def latency_report(requests: List[Request]) -> Dict[str, float]:
        """Latency summary; zeros on an empty list (a shed-everything
        overload window is a valid report, not a crash).  Requests shed
        before serving (``first_token_at == 0``) are excluded from the
        TTFT mean/percentiles and counted in ``n_timed_out``.

        The p50/p95/p99 tails come from `repro.obs.metrics.Histogram` —
        the same class (and the same sample definitions, see
        `_record_retired`) behind the ``serving.ttft_us`` /
        ``serving.token_us`` series in the process registry, so this
        report and a telemetry export never disagree on the math."""
        zeros = {
            "n_requests": 0,
            "n_timed_out": 0,
            "ttft_mean_s": 0.0,
            "ttft_p50_s": 0.0,
            "ttft_p95_s": 0.0,
            "ttft_p99_s": 0.0,
            "latency_mean_s": 0.0,
            "token_p50_s": 0.0,
            "token_p95_s": 0.0,
            "token_p99_s": 0.0,
            "tokens_total": 0,
            "tokens_per_s": 0.0,
        }
        if not requests:
            return zeros
        hist = obs_metrics.Histogram("latency_report")
        for r in requests:
            if r.first_token_at > 0:
                hist.observe(r.first_token_at - r.submitted_at, kind="ttft")
                n_out = len(r.output or [])
                if n_out > 1:
                    hist.observe(
                        (r.done_at - r.first_token_at) / (n_out - 1),
                        kind="token",
                    )
        ttft = hist.summary(kind="ttft")
        token = hist.summary(kind="token")
        total = [r.done_at - r.submitted_at for r in requests]
        n_tok = sum(len(r.output or []) for r in requests)
        wall = max(r.done_at for r in requests) - min(r.submitted_at for r in requests)
        return {
            "n_requests": len(requests),
            "n_timed_out": sum(1 for r in requests if r.status == "timed_out"),
            "ttft_mean_s": ttft["mean"],
            "ttft_p50_s": ttft["p50"],
            "ttft_p95_s": ttft["p95"],
            "ttft_p99_s": ttft["p99"],
            "latency_mean_s": float(np.mean(total)),
            "token_p50_s": token["p50"],
            "token_p95_s": token["p95"],
            "token_p99_s": token["p99"],
            "tokens_total": n_tok,
            "tokens_per_s": n_tok / wall if wall > 0 else float("inf"),
        }
