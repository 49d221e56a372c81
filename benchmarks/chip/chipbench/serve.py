"""Serving cells: a decoder LM behind the program's `ServingEngine`.

Traffic ``closed_loop``: each round submits ``batch`` prompts of
``prompt_len`` tokens drawn from the seed, each asking ``max_new_tokens``,
and hands them to `ServingEngine.run`; the next round starts when it
returns.  The engine runs as users run it: ``gemm_backend="sfc_pallas"``,
``attn_impl`` as the configuration states, ``REPRO_STRICT=1``.

`Recorder` wraps the engine's jitted prefill and decode programs.  The
engine syncs on every token before it launches the next step, so the host
time at which decode call k+1 starts is the time token k reached the host;
the last token's is the request's ``done_at``.  The logits of the rounds
the check samples are kept as the programs produced them.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import pathlib
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import compare, load_module, weights
from chipbench.trace import WINDOW_SPAN

REFERENCES = pathlib.Path(__file__).resolve().parents[1] / "references"


def load_reference(name: str):
    """``references/<name>.py``: everything of a configuration that depends on
    its architecture.  It provides

    - ``params(model, seed)``: the benchmark's own weights from the seed,
      laid out as the program's ``init`` lays them out;
    - ``logits(params, model, tokens, positions, *, precision="f32")``: the
      plain reference's logits, and with ``precision="fp8"`` the control's;
    - ``round_work(batch, prompt_len, max_new, model, peaks)``: the numbers
      one closed-loop round's work comes to, at least ``flops`` and
      ``gemm_least_s``, and any others its metric readers take."""
    return load_module(REFERENCES / f"{name}.py", f"reference_{name}")


@dataclasses.dataclass
class Round:
    requests: list
    starts: List[float]      # host time each decode call started
    logits: List[jax.Array]  # kept rounds: prefill's, then each decode's

    def gaps(self) -> List[float]:
        """Seconds between successive tokens of every request."""
        out: List[float] = []
        for r in self.requests:
            times = self.starts[:len(r.output) - 1] + [r.done_at]
            out += list(np.diff(times))
        return out


class Recorder:
    def __init__(self, engine):
        self._prefill, self._decode = engine._prefill, engine._decode
        engine._prefill, engine._decode = self.prefill, self.decode
        self.keep = False
        self.starts: List[float] = []
        self.logits: List[jax.Array] = []

    def begin(self, keep: bool) -> None:
        self.keep, self.starts, self.logits = keep, [], []

    def prefill(self, params, tokens):
        out = self._prefill(params, tokens)
        if self.keep:
            self.logits.append(out[0])
        return out

    def decode(self, params, token, cache):
        self.starts.append(time.perf_counter())
        out = self._decode(params, token, cache)
        if self.keep:
            self.logits.append(out[0])
        return out


class Cell:
    """One serving cell: the engine with its weights, warmed up."""

    def __init__(self, name: str, config: dict, traffic: dict, seed: int):
        from repro.configs.base import ArchConfig
        from repro.models.registry import build_model
        from repro.serving.engine import ServingEngine

        self.name, self.config, self.traffic = name, config, traffic
        self.model = config["model"]
        fields = {f.name for f in dataclasses.fields(ArchConfig)}
        self.cfg = ArchConfig(**{k: v for k, v in self.model.items()
                                 if k in fields})
        self.reference = load_reference(config["reference"])
        t0 = time.perf_counter()
        self.params = self.make_params(seed)
        self.setup_parts = {"weights_s": time.perf_counter() - t0}
        want = jax.eval_shape(build_model(self.cfg).init, jax.random.PRNGKey(0))
        got = jax.eval_shape(lambda: self.params)
        if jax.tree.structure(want) != jax.tree.structure(got) or any(
                (w.shape, w.dtype) != (g.shape, g.dtype)
                for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
            raise SystemExit(f"{name}: the benchmark's weights do not match "
                             f"the program's parameter layout")
        self.engine = ServingEngine(
            self.cfg, self.params, max_batch=traffic["max_batch"],
            max_seq=traffic["max_seq"], gemm_backend="sfc_pallas")
        self.recorder = Recorder(self.engine)
        # every program of the cell's traffic, compiled (or read from the
        # cache) before the window: one round of its shapes, one decode step
        t0 = time.perf_counter()
        warm = self.prompts(weights.host_rng(seed, "warmup"))
        self.round(warm, keep=False, max_new=min(2, traffic["max_new_tokens"]))
        self.setup_parts["warmup_s"] = time.perf_counter() - t0

    def make_params(self, seed: int):
        params = self.reference.params(self.model, seed)
        jax.block_until_ready(params)
        return params

    def prompts(self, rng: np.random.Generator) -> List[np.ndarray]:
        t = self.traffic
        return list(rng.integers(0, self.model["vocab"],
                                 (t["batch"], t["prompt_len"]), dtype=np.int32))

    def round(self, prompts, *, keep: bool, max_new: Optional[int] = None) -> Round:
        max_new = max_new or self.traffic["max_new_tokens"]
        self.recorder.begin(keep)
        reqs = self.engine.submit_many(prompts, max_new_tokens=max_new)
        done = self.engine.run(reqs)
        return Round(done, self.recorder.starts, self.recorder.logits)

    # ------------------------------------------------------------------

    def kept_plan(self, seed: int, n_rounds_max: Optional[int] = None):
        """Which rounds keep their logits, and which rows of each are
        checked: drawn from the seed among the first rounds."""
        chk = self.traffic["check"]
        pool = chk["of_first"] if n_rounds_max is None else min(
            chk["of_first"], n_rounds_max)
        rng = weights.host_rng(seed, "check")
        rounds = sorted(int(i) for i in rng.choice(pool, chk["rounds"],
                                                   replace=False))
        rows = {i: sorted(int(j) for j in rng.choice(
            self.traffic["batch"], chk["rows"], replace=False)) for i in rounds}
        return rows

    def window(self, seed: int, seconds: float, *, n_rounds: Optional[int] = None,
               annotate=None) -> Dict:
        """Closed-loop rounds for ``seconds`` (or exactly ``n_rounds``): a
        round starts while the window is open, and until every kept round
        has run; the last one may end after the window."""
        rng = weights.host_rng(seed, "traffic")
        plan = self.kept_plan(seed, n_rounds)
        last_kept = max(plan)
        rounds: List[Round] = []
        span = annotate or (lambda name: contextlib.nullcontext())
        gc.collect()
        gc.disable()
        t0 = time.perf_counter()
        t_end = t0 + seconds
        try:
            with span(WINDOW_SPAN):
                while True:
                    i = len(rounds)
                    if n_rounds is not None:
                        if i >= n_rounds:
                            break
                    elif i > last_kept and time.perf_counter() >= t_end:
                        break
                    prompts = self.prompts(rng)
                    with span("bench/round"):
                        rounds.append(self.round(prompts, keep=i in plan))
        finally:
            gc.enable()
        return {"t0": t0, "rounds": rounds, "plan": plan}

    # ------------------------------------------------------------------

    def metrics(self, win: Dict) -> Dict[str, float]:
        """End-to-end metrics of a window, by the host clock."""
        reqs = [r for rd in win["rounds"] for r in rd.requests]
        done = [r for r in reqs if r.status == "completed"]
        span = max(r.done_at for r in done) - win["t0"]
        gaps = [g for rd in win["rounds"] for g in rd.gaps()]
        out = {
            "prefill_tok_s": sum(len(r.prompt) for r in done) / span,
            "decode_tok_s": sum(len(r.output) for r in done) / span,
        }
        if gaps:
            out["itl_p95_ms"] = float(np.percentile(gaps, 95)) * 1e3
        out["_attempted"] = len(reqs)
        out["_failed"] = len(reqs) - len(done)
        return out

    def work(self, win: Dict, peaks: Dict[str, float]) -> Dict[str, float]:
        """The reference's work counts of one round, times the window's
        rounds."""
        t = self.traffic
        one = self.reference.round_work(t["batch"], t["prompt_len"],
                                        t["max_new_tokens"], self.model, peaks)
        n = len(win["rounds"])
        return {"rounds": n, **{k: v * n for k, v in one.items()}}

    def drop_engine(self) -> None:
        """Free the engine's state before the reference runs."""
        self.engine = self.recorder = None
        gc.collect()

    def check(self, win: Dict, *, control: bool = False) -> Dict[str, float]:
        """The compared numbers of the sampled requests: the widest gap of a
        served token under the reference, and the largest relative error of
        a request's kept logits.  With ``control`` also the same numbers of
        the fp8 control: the gap of the token it puts first at each
        position, and its logits' error."""
        block = self.traffic["check"]["block"]
        n = self.traffic["max_new_tokens"]
        # an answer that never came: tokens short of (or past) what each
        # request of the window asked for
        out = {"missing_tokens": float(sum(
            abs(n - len(r.output or [])) for rd in win["rounds"]
            for r in rd.requests)), "logit_gap": 0.0, "logit_rel_err": 0.0}
        if out["missing_tokens"]:
            return out
        if control:
            out.update(control_logit_gap=0.0, control_logit_rel_err=0.0)
        for i, rows in win["plan"].items():
            rd = win["rounds"][i]
            kept = jnp.stack([lg[jnp.asarray(rows)] for lg in rd.logits], 1)
            for b0 in range(0, len(rows), block):
                sel = rows[b0:b0 + block]
                reqs = [rd.requests[j] for j in sel]
                s = len(reqs[0].prompt)
                tokens = np.stack([np.concatenate([r.prompt, r.output[:-1]])
                                   for r in reqs]).astype(np.int32)
                served = np.asarray([r.output for r in reqs], np.int32)
                positions = np.arange(s - 1, s - 1 + n)
                ref = self.reference.logits(self.params, self.model, tokens,
                                            positions)
                out["logit_gap"] = max(out["logit_gap"], float(
                    jnp.max(compare.token_gaps(ref, served))))
                prog = kept[b0:b0 + block]
                for j in range(len(sel)):
                    out["logit_rel_err"] = max(out["logit_rel_err"],
                                               compare.rel_err(prog[j], ref[j]))
                if control:
                    ctl = self.reference.logits(self.params, self.model, tokens,
                                                positions, precision="fp8")
                    out["control_logit_gap"] = max(
                        out["control_logit_gap"], float(jnp.max(
                            compare.token_gaps(ref, jnp.argmax(ctl, -1)))))
                    for j in range(len(sel)):
                        out["control_logit_rel_err"] = max(
                            out["control_logit_rel_err"],
                            compare.rel_err(ctl[j], ref[j]))
                    del ctl
                del ref
        return out
