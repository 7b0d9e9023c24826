"""GPipe-style pipeline parallelism as a shard_map + collective_permute scan.

``pipeline_apply`` runs ``stage_fn`` over ``S`` pipeline stages (one per mesh
slice along ``axis``) with ``M`` microbatches. The schedule is the classic
GPipe fill-drain: ``M + S - 1`` ticks; at tick ``t`` stage ``s`` processes
microbatch ``t - s``. Activations move stage→stage via ``collective_permute``
(a neighbour ICI transfer, overlappable by XLA with the stage compute).

Bubble fraction = (S-1)/(M+S-1) — the launcher warns when M < 4·S. Used as an
*alternative* to pod-level DP for the multi-pod mesh (see DESIGN.md §5); the
dry-run exercises it via launch/dryrun.py --pipeline.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P


def pipeline_apply(stage_fn: Callable, stage_params: Any, x: jax.Array, *,
                   mesh: Mesh, axis: str = "pod", microbatches: int = 8
                   ) -> jax.Array:
    """Run a layer-partitioned model as a pipeline.

    stage_fn(params_slice, x_mb) -> y_mb, applied S times in sequence overall.
    ``stage_params``: pytree with leading dim S (= mesh.shape[axis]).
    ``x``: (B, ...) global batch; split into M microbatches along axis 0.
    """
    S = mesh.shape[axis]
    M = microbatches
    B = x.shape[0]
    assert B % M == 0, (B, M)
    mb = B // M
    x_mb = x.reshape((M, mb) + x.shape[1:])

    def per_stage(params_s, x_all):
        # params_s: this stage's params (leading dim 1 from shard_map)
        params_s = jax.tree.map(lambda a: a[0], params_s)
        idx = jax.lax.axis_index(axis)
        T = M + S - 1
        buf = jnp.zeros((mb,) + x.shape[1:], x.dtype)
        outs = jnp.zeros((M, mb) + x.shape[1:], x.dtype)

        def tick(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t (if t < M); others take the
            # neighbour's output from the previous tick (already in buf).
            feed = jax.lax.dynamic_index_in_dim(
                x_all, jnp.minimum(t, M - 1), axis=0, keepdims=False)
            inp = jnp.where(idx == 0, feed, buf)
            y = stage_fn(params_s, inp)
            # pass to next stage
            perm = [(i, (i + 1) % S) for i in range(S)]
            buf = jax.lax.ppermute(y, axis, perm)
            # last stage emits microbatch t - (S-1)
            emit_idx = jnp.clip(t - (S - 1), 0, M - 1)
            outs = jax.lax.cond(
                t >= S - 1,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, emit_idx, axis=0),
                lambda o: o, outs)
            return buf, outs

        _, outs = jax.lax.fori_loop(0, T, tick, (buf, outs))
        # only the last stage's outs are real; broadcast them to all stages
        # (psum over one-hot mask keeps a single collective)
        mask = (idx == S - 1).astype(outs.dtype)
        outs = jax.lax.psum(outs * mask, axis)
        return outs

    other_axes = tuple(a for a in mesh.axis_names if a != axis)
    in_specs = (P(axis), P())
    out_specs = P()
    fn = jax.shard_map(per_stage, mesh=mesh, in_specs=in_specs,
                    out_specs=out_specs, check_vma=False)
    outs = fn(stage_params, x_mb)
    return outs.reshape((B,) + x.shape[1:])


def bubble_fraction(n_stages: int, microbatches: int) -> float:
    return (n_stages - 1) / (microbatches + n_stages - 1)
