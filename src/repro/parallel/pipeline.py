"""Pipeline parallelism: GPipe-style microbatch pipeline over a mesh axis.

`pipeline_apply` runs a stack of per-stage functions over a chosen mesh
axis ("pod" in the multi-pod mesh, or a dedicated "pipe" axis): stage s
lives on shard s of the axis, microbatches rotate through stages with
`ppermute`, and the classic GPipe schedule (fill, steady state, drain)
falls out of a single `lax.scan` over n_micro + n_stages - 1 ticks.

All stages execute every tick (SPMD), with masking for the fill/drain
bubbles — utilization = n_micro / (n_micro + n_stages - 1), the GPipe
bubble formula, which `tests/test_pipeline.py` asserts against the
collective-permute count.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["pipeline_apply", "stage_params_spec"]


def stage_params_spec(axis: str):
    """PartitionSpec for per-stage parameter stacks: leading stage dim over
    the pipeline axis (one stage's params per shard)."""

    def spec(leaf):
        return P(axis, *([None] * (np.ndim(leaf) - 1)))

    return spec


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,  # pytree, leaves (n_stages, ...) — sharded over `axis`
    x: jax.Array,  # (n_micro, micro_batch, ...) microbatched input
    *,
    mesh: Mesh,
    axis: str,
    data_spec: P = P(),
) -> jax.Array:
    """Run x through n_stages pipeline stages laid over mesh axis `axis`.

    stage_fn(params_for_stage, h) -> h  must be shape-preserving (a standard
    transformer block stack satisfies this; embed/head live outside).
    Returns the (n_micro, micro_batch, ...) outputs.
    """
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    ticks = n_micro + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def body(params_loc, x_loc):
        # params_loc: (1, ...) leaves — this shard's stage params
        params_mine = jax.tree.map(lambda p: p[0], params_loc)
        stage_id = lax.axis_index(axis)
        buf = jnp.zeros_like(x_loc[0])  # current microbatch flowing through
        outs = jnp.zeros_like(x_loc)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (while t < n_micro)
            ingest = jnp.where(t < n_micro, jnp.minimum(t, n_micro - 1), 0)
            fresh = lax.dynamic_index_in_dim(x_loc, ingest, keepdims=False)
            buf = jnp.where(stage_id == 0, jnp.where(t < n_micro, fresh, buf), buf)
            # every stage processes its resident microbatch
            h = stage_fn(params_mine, buf)
            # last stage emits microbatch (t - n_stages + 1) when valid
            emit_idx = t - (n_stages - 1)
            valid = (stage_id == n_stages - 1) & (emit_idx >= 0)
            outs = lax.cond(
                valid,
                lambda o: lax.dynamic_update_index_in_dim(
                    o, h, jnp.maximum(emit_idx, 0), axis=0
                ),
                lambda o: o,
                outs,
            )
            # rotate: stage s hands its activation to stage s+1
            buf = lax.ppermute(h, axis, perm)
            return (buf, outs), None

        (_, outs), _ = lax.scan(tick, (buf, outs), jnp.arange(ticks))
        # outs live on the last stage; broadcast to all shards for output
        outs = lax.psum(
            jnp.where(stage_id == n_stages - 1, outs, jnp.zeros_like(outs)), axis
        )
        return outs

    p_specs = jax.tree.map(lambda l: stage_params_spec(axis)(l), stage_params)
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(p_specs, data_spec),
        out_specs=data_spec,
        check_vma=False,
    )(stage_params, x)
