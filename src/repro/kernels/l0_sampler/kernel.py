"""Pallas TPU kernel: ℓ0-sampler sketch delta from a signed edge batch.

Same shape of solution as the Count-Sketch kernel: the data-dependent
scatter becomes a one-hot accumulate with the counter state resident in
VMEM across the edge-block grid dimension.  Two differences forced by the
ℓ0 structure:

* the flattened column space is ``L*C`` (levels × cells), far bigger than
  a Count-Sketch table, so the output is ALSO blocked over columns —
  grid ``(d, n_col_blocks, n_edge_blocks)`` with the edge dimension
  innermost, zero-init at ``eb == 0`` exactly like the Count-Sketch
  ``(t, n_edge_blocks)`` pattern;
* the four cell fields (count, sum_u, sum_v, fingerprint) are int32 with
  wrap-around semantics, and int32 matmul is not an MXU citizen — the
  one-hot contraction is a broadcast-multiply-sum on the VPU instead of
  ``jnp.dot``, chunked over columns to bound the live intermediate
  (``[4, block_e, col_chunk]`` int32).

Fields ride in sublane rows 0:4 of an (8, cols) block (sublane padding as
in the Count-Sketch kernel); the wrapper transposes back to the canonical
``[L, d, C, 4]`` sketch layout.

Cost model: a dense one-hot scatter is Θ(E · L·C) work per table, so the
kernel wants BATCHED updates (the turnstile driver pads batches to pow2
buckets precisely so this program caches and amortizes); the dispatch
rule keeps CPU runs on the segment-sum reference, which is the right
algorithm there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import hashing
from repro.kernels.l0_sampler.ops import level_from_hash


def _l0_kernel(
    u_ref,
    v_ref,
    s_ref,
    al_ref,
    cl_ref,
    af_ref,
    cf_ref,
    ac_ref,
    cc_ref,
    out_ref,
    *,
    n_levels,
    n_cells,
    block_c,
    col_chunk,
):
    j = pl.program_id(0)
    cb = pl.program_id(1)
    eb = pl.program_id(2)

    @pl.when(eb == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    u = u_ref[0, :]
    v = v_ref[0, :]
    s = s_ref[0, :]
    uu = u.astype(jnp.uint32)
    vv = v.astype(jnp.uint32)

    # Shared pair-hash family (plain uint32 jnp ops, traceable here) —
    # bit-identical to the ops.py / ref.py spelling.
    h_lvl = hashing.mix32_pair(al_ref[0], al_ref[1], cl_ref[0], uu, vv)
    lvl = level_from_hash(h_lvl, n_levels)
    fp = hashing.mix32_pair(af_ref[0], af_ref[1], cf_ref[0], uu, vv)
    fp_i = jax.lax.bitcast_convert_type(fp, jnp.int32)
    cell = hashing.bucket32(
        hashing.mix32_pair(ac_ref[j, 0], ac_ref[j, 1], cc_ref[j], uu, vv), n_cells
    )

    # Flattened (level, cell) column, local to this column block.
    local = lvl * n_cells + cell - cb * block_c  # int32[E]
    vals = jnp.stack([s, s * u, s * v, s * fp_i])  # int32[4, E]

    def body(c, _):
        cols = (
            jax.lax.broadcasted_iota(jnp.int32, (local.shape[0], col_chunk), 1)
            + c * col_chunk
        )
        onehot = (local[:, None] == cols).astype(jnp.int32)  # [E, chunk]
        partial = jnp.sum(vals[:, :, None] * onehot[None, :, :], axis=1)  # [4, chunk]
        idx = pl.dslice(c * col_chunk, col_chunk)
        out_ref[0, 0:4, idx] += partial
        return _

    jax.lax.fori_loop(0, block_c // col_chunk, body, 0)


@functools.partial(
    jax.jit,
    static_argnames=("n_levels", "n_cells", "block_e", "block_c", "col_chunk", "interpret"),
)
def l0_delta_pallas(
    u: jax.Array,  # int32[E] canonical min endpoint
    v: jax.Array,  # int32[E] canonical max endpoint
    sgn: jax.Array,  # int32[E] ±1 insert/delete, 0 padding
    a_lvl: jax.Array,  # uint32[2]
    c_lvl: jax.Array,  # uint32[1]
    a_fp: jax.Array,  # uint32[2]
    c_fp: jax.Array,  # uint32[1]
    a_cell: jax.Array,  # uint32[d, 2]
    c_cell: jax.Array,  # uint32[d]
    *,
    n_levels: int,
    n_cells: int,
    block_e: int = 256,
    block_c: int | None = None,
    col_chunk: int = 256,
    interpret: bool | None = None,  # None: compiled on TPU, interpreter elsewhere
) -> jax.Array:
    """Returns the sketch delta int32[L, d, C, 4]."""
    from repro.kernels import resolve_interpret

    interpret = resolve_interpret(interpret)
    e = u.shape[0]
    d = a_cell.shape[0]
    n_cols = n_levels * n_cells
    if block_c is None:
        block_c = min(n_cols, 4096)
    col_chunk = min(col_chunk, block_c)
    assert e % block_e == 0, (e, block_e)
    assert n_cols % block_c == 0, (n_cols, block_c)
    assert block_c % col_chunk == 0, (block_c, col_chunk)
    n_eb = e // block_e
    n_cb = n_cols // block_c

    u2 = u.reshape(1, e)
    v2 = v.reshape(1, e)
    s2 = sgn.astype(jnp.int32).reshape(1, e)

    kern = functools.partial(
        _l0_kernel,
        n_levels=n_levels,
        n_cells=n_cells,
        block_c=block_c,
        col_chunk=col_chunk,
    )
    # The hash parameters are scalars: whole arrays in SMEM, indexed by the
    # table's grid row inside the kernel (the TPU lowering refuses rank-1
    # (1,)-blocks and a (1, 2) block of the (d, 2) multiplier array).
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        kern,
        grid=(d, n_cb, n_eb),
        in_specs=[
            pl.BlockSpec((1, block_e), lambda j, c_, e_: (0, e_)),
            pl.BlockSpec((1, block_e), lambda j, c_, e_: (0, e_)),
            pl.BlockSpec((1, block_e), lambda j, c_, e_: (0, e_)),
        ]
        + [smem] * 6,
        out_specs=pl.BlockSpec((1, 8, block_c), lambda j, c_, e_: (j, 0, c_)),
        out_shape=jax.ShapeDtypeStruct((d, 8, n_cols), jnp.int32),
        interpret=interpret,
    )(u2, v2, s2, a_lvl, c_lvl, a_fp, c_fp, a_cell, c_cell)
    # (d, 4, L*C) -> (d, 4, L, C) -> canonical [L, d, C, 4].
    return out[:, 0:4, :].reshape(d, 4, n_levels, n_cells).transpose(2, 0, 3, 1)
