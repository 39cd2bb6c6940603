"""Pallas TPU kernel: Count-Sketch counter update from an endpoint stream.

Paper §5.1 maintains t tables of b signed counters; every edge endpoint x
does ``c[i, h_i(x)] += g_i(x) * w``.  On TPU the data-dependent scatter
becomes a one-hot matmul, and — unlike the degree kernel — no pre-bucketing
is needed because the whole counter table is VMEM-resident (that is the
*point* of the sketch: O(t*b) state).

Grid: (t, n_endpoint_blocks).  Each step:
  * hashes one endpoint block with the table's multiply-shift parameters
    (uint32 arithmetic on the VPU),
  * builds onehot[e, c] = (bucket[e] == c) over the b counter columns,
  * accumulates ``(w * sign)[1, E] @ onehot[E, b]`` on the MXU into the
    table's counter row, which stays in VMEM across the block dimension.

VMEM per step (E_blk=512, b=8192): onehot 16 MB f32 is too big, so the
one-hot matmul is done in column chunks of 2048 inside the kernel
(fori_loop), keeping the live window ~4 MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import hashing


def _cs_kernel(x_ref, w_ref, ah_ref, ch_ref, ag_ref, cg_ref, out_ref, *, n_buckets, col_chunk):
    i = pl.program_id(0)
    eb = pl.program_id(1)

    @pl.when(eb == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[0, :].astype(jnp.uint32)
    w = w_ref[0, :]
    a_h = ah_ref[i]
    c_h = ch_ref[i]
    a_g = ag_ref[i]
    c_g = cg_ref[i]

    # Shared multiply-shift family (plain uint32 jnp ops, traceable here).
    bucket = hashing.bucket32(hashing.mix32(a_h, c_h, x), n_buckets)
    sign = hashing.sign32(hashing.mix32(a_g, c_g, x))
    val = (w * sign)[None, :]  # [1, E]

    n_chunks = n_buckets // col_chunk

    def body(c, _):
        cols = jax.lax.broadcasted_iota(
            jnp.int32, (bucket.shape[0], col_chunk), 1
        ) + c * col_chunk
        onehot = (bucket[:, None] == cols).astype(jnp.float32)
        partial = jnp.dot(val, onehot, preferred_element_type=jnp.float32)
        idx = pl.dslice(c * col_chunk, col_chunk)
        out_ref[0, 0:1, idx] += partial
        return _

    jax.lax.fori_loop(0, n_chunks, body, 0)


@functools.partial(
    jax.jit, static_argnames=("n_buckets", "block_e", "col_chunk", "interpret")
)
def count_sketch_update_pallas(
    endpoints: jax.Array,  # int32[E] endpoint node ids (stream order)
    w: jax.Array,  # float32[E] weight contribution (0 for dead/padding)
    a_h: jax.Array,  # uint32[t]
    c_h: jax.Array,  # uint32[t]
    a_g: jax.Array,  # uint32[t]
    c_g: jax.Array,  # uint32[t]
    *,
    n_buckets: int,
    block_e: int = 512,
    col_chunk: int = 2048,
    interpret: bool | None = None,  # None: compiled on TPU, interpreter elsewhere
) -> jax.Array:
    """Returns float32[t, n_buckets] counter tables."""
    from repro.kernels import resolve_interpret

    interpret = resolve_interpret(interpret)
    e = endpoints.shape[0]
    t = a_h.shape[0]
    assert e % block_e == 0, (e, block_e)
    col_chunk = min(col_chunk, n_buckets)
    assert n_buckets % col_chunk == 0
    n_eb = e // block_e

    x2 = endpoints.reshape(1, e)
    w2 = w.reshape(1, e)

    kern = functools.partial(_cs_kernel, n_buckets=n_buckets, col_chunk=col_chunk)
    # Per-table hash scalars live whole in SMEM, indexed by the grid row (the
    # TPU lowering refuses rank-1 (1,)-blocks).
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        kern,
        grid=(t, n_eb),
        in_specs=[
            pl.BlockSpec((1, block_e), lambda i, e_: (0, e_)),
            pl.BlockSpec((1, block_e), lambda i, e_: (0, e_)),
        ]
        + [smem] * 4,
        out_specs=pl.BlockSpec((1, 8, n_buckets), lambda i, e_: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((t, 8, n_buckets), jnp.float32),
        interpret=interpret,
    )(x2, w2, a_h, c_h, a_g, c_g)
    return out[:, 0, :]
