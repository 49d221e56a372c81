"""Distributed GEMM cells: the program's 2.5D `ca_matmul` on a device mesh.

Traffic ``back_to_back``: the same product is called again and again; a
round launches ``calls_per_round`` calls and syncs once, on the last.  The
operands are made on the devices from the seed, laid out as `ca_matmul`
expects them (A: M over tm, K over kl; B: K over kl, N over tn).
"""

from __future__ import annotations

import contextlib
import gc
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from chipbench import compare, flops, weights
from chipbench.serve import load_reference
from chipbench.trace import WINDOW_SPAN


class Cell:
    def __init__(self, name: str, config: dict, traffic: dict, seed: int,
                 devices):
        from repro.core.ca_matmul import ca_matmul

        self.name, self.config, self.traffic = name, config, traffic
        g = config["gemm"]
        self.shape = (g["m"], g["n"], g["k"])
        self.dtype = jnp.dtype(g["dtype"])
        mesh_shape = tuple(g["mesh"][a] for a in ("tm", "tn", "kl"))
        self.mesh = Mesh(np.asarray(devices).reshape(mesh_shape),
                         ("tm", "tn", "kl"))
        self.a_sharding = NamedSharding(self.mesh, P("tm", "kl"))
        self.b_sharding = NamedSharding(self.mesh, P("kl", "tn"))
        self.reference = load_reference(config["reference"])
        self.program = jax.jit(lambda a, b: ca_matmul(
            a, b, mesh=self.mesh, tm_axis="tm", tn_axis="tn", kl_axis="kl",
            backend=g["backend"], reduce=g["reduce"]))
        t0 = time.perf_counter()
        self.operands(seed)
        self.setup_parts = {"weights_s": time.perf_counter() - t0}
        # the one program of the window, compiled before it
        t0 = time.perf_counter()
        jax.block_until_ready(self.program(self.a, self.b))
        self.setup_parts["warmup_s"] = time.perf_counter() - t0

    def operands(self, seed: int) -> None:
        m, n, k = self.shape
        dt = self.dtype

        def make(key):
            ka, kb = jax.random.split(key)
            return (jax.random.normal(ka, (m, k), jnp.float32).astype(dt),
                    jax.random.normal(kb, (k, n), jnp.float32).astype(dt))

        self.a, self.b = jax.jit(make, out_shardings=(
            self.a_sharding, self.b_sharding))(weights.root_key(seed))
        jax.block_until_ready((self.a, self.b))

    def kept_plan(self, seed: int, n_calls_max: Optional[int] = None) -> List[int]:
        chk = self.traffic["check"]
        pool = chk["of_first"] if n_calls_max is None else min(
            chk["of_first"], n_calls_max)
        rng = weights.host_rng(seed, "check")
        return sorted(int(i) for i in rng.choice(pool, chk["calls"],
                                                 replace=False))

    def window(self, seed: int, seconds: float, *, n_rounds: Optional[int] = None,
               annotate=None) -> Dict:
        """Rounds of back-to-back calls for ``seconds`` (or ``n_rounds``);
        the outputs of the sampled calls are kept."""
        per = self.traffic["calls_per_round"]
        plan = self.kept_plan(seed, None if n_rounds is None else n_rounds * per)
        kept: Dict[int, jax.Array] = {}
        ends: List[float] = []
        calls = 0
        span = annotate or (lambda name: contextlib.nullcontext())
        gc.collect()
        gc.disable()
        t0 = time.perf_counter()
        t_end = t0 + seconds
        try:
            with span(WINDOW_SPAN):
                while True:
                    if n_rounds is not None:
                        if len(ends) >= n_rounds:
                            break
                    elif calls > plan[-1] and time.perf_counter() >= t_end:
                        break
                    with span("bench/round"):
                        self._round(per, calls, plan, kept)
                    calls += per
                    ends.append(time.perf_counter())
        finally:
            gc.enable()
        return {"t0": t0, "ends": ends, "calls": calls, "kept": kept}

    def _round(self, per, first, plan, kept) -> None:
        for i in range(first, first + per):
            out = self.program(self.a, self.b)
            if i in plan:
                kept[i] = out
        out.block_until_ready()

    def metrics(self, win: Dict) -> Dict[str, float]:
        m, n, k = self.shape
        span = win["ends"][-1] - win["t0"]
        return {"gemm_tflops": 2.0 * m * n * k * win["calls"] / span / 1e12,
                "_attempted": win["calls"], "_failed": 0}

    def work(self, win: Dict, peaks: Dict[str, float]) -> Dict[str, float]:
        m, n, k = self.shape
        g = self.config["gemm"]["mesh"]
        local = flops.Gemm(m // g["tm"], n // g["tn"], k // g["kl"])
        return {"calls": win["calls"], "flops": local.flops * g["tm"] * g["tn"]
                * g["kl"] * win["calls"],
                "gemm_least_s": local.least_s(peaks) * win["calls"],
                "n_devices": g["tm"] * g["tn"] * g["kl"]}

    def drop_engine(self) -> None:
        gc.collect()

    def check(self, win: Dict, *, control: bool = False) -> Dict[str, float]:
        """Relative error of each sampled call's whole output against the
        reference (the largest); with ``control``, the fp8 control's."""
        out = {"c_rel_err": 0.0}
        ref = None
        for i, c in sorted(win["kept"].items()):
            if ref is None:
                ref = self.reference.product(self.a, self.b, c.sharding)
            out["c_rel_err"] = max(out["c_rel_err"], compare.rel_err(c, ref))
        if control:
            ctl = self.reference.product(self.a, self.b, ref.sharding,
                                         precision="fp8")
            out["control_c_rel_err"] = compare.rel_err(ctl, ref)
        return out
