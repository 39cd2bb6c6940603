"""GPipe-style pipeline parallelism over a mesh axis via shard_map +
collective_permute.

The layer stack is partitioned into ``n_stages`` contiguous stages placed
along one mesh axis (the multi-pod mesh's 'pod' axis: cross-pod links are
the slowest, and pipeline traffic — one activation tensor per microbatch
per boundary — is the lightest cross-cut of the model, which is why PP is
the standard inter-pod axis).  Microbatches stream through stages in the
classic GPipe schedule:

    for t in range(n_micro + n_stages - 1):      # pipeline "ticks"
        each stage processes microbatch (t - stage) if in range
        boundary activations shift stage -> stage+1 via ppermute

Implemented as a ``lax.scan`` over ticks inside ``shard_map``; bubbles are
the (n_stages - 1) / (n_micro + n_stages - 1) idle fraction, reported by
``bubble_fraction`` and validated in tests.  The backward pass is jax AD
through the scan (activations stashed per tick — classic GPipe memory).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def pipelined_apply(
    mesh: Mesh,
    axis: str,
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,  # pytree with leading [n_stages] dim, sharded on axis
    x: jax.Array,  # [n_micro, micro_batch, ...] microbatched input
) -> jax.Array:
    """Runs x through n_stages pipeline stages laid out along ``axis``.

    stage_fn(params_for_stage, h) -> h must preserve h's shape (the
    transformer-layer contract); stage s applies layers [s*L/S, (s+1)*L/S).
    Returns [n_micro, micro_batch, ...] outputs (from the LAST stage,
    broadcast to all shards for loss computation).
    """
    n_stages = mesh.shape[axis]

    def local(params, xs):  # params: [1, ...] slice; xs: [n_micro, mb, ...]
        params = jax.tree.map(lambda p: p[0], params)
        stage = jax.lax.axis_index(axis)
        n_micro = xs.shape[0]
        n_ticks = n_micro + n_stages - 1
        mb_shape = xs.shape[1:]

        def tick(carry, t):
            outputs, inbuf = carry
            # Which microbatch this stage works on at tick t.
            mb_idx = t - stage
            active = (mb_idx >= 0) & (mb_idx < n_micro)
            # Stage 0 reads from the input stream, others from inbuf.
            x_in = jnp.where(
                stage == 0,
                xs[jnp.clip(mb_idx, 0, n_micro - 1)],
                inbuf,
            )
            h = stage_fn(params, x_in)
            h = jnp.where(active, h, jnp.zeros_like(h))
            # Last stage writes its result to the output stream.
            outputs = jax.lax.cond(
                active & (stage == n_stages - 1),
                lambda o: o.at[jnp.clip(mb_idx, 0, n_micro - 1)].set(h),
                lambda o: o,
                outputs,
            )
            # Shift boundary activations stage -> stage + 1.
            nxt = jax.lax.ppermute(
                h, axis, [(i, i + 1) for i in range(n_stages - 1)]
            )
            return (outputs, nxt), None

        outputs = jnp.zeros((n_micro,) + mb_shape, xs.dtype)
        inbuf = jnp.zeros(mb_shape, xs.dtype)
        (outputs, _), _ = jax.lax.scan(
            tick, (outputs, inbuf), jnp.arange(n_ticks)
        )
        # Broadcast final outputs from the last stage to every shard
        # (masked psum — ppermute needs unique destinations).
        outputs = jnp.where(stage == n_stages - 1, outputs, jnp.zeros_like(outputs))
        outputs = jax.lax.psum(outputs, axis)
        return outputs

    other = tuple(a for a in mesh.axis_names if a != axis)
    del other
    return shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, x)
