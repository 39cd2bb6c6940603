"""Pallas TPU kernel: per-pass degree histogram over tile-bucketed edges.

The paper's per-pass hot spot is the reduce-side degree count.  TPUs have no
efficient data-dependent scatter, so the scatter becomes MXU work:

  * edges were bucketed by target-node TILE once (graph/partition.py — the
    'shuffle', done one time, not per pass);
  * each grid step loads one (tile, edge-block) pair into VMEM, builds the
    one-hot matrix ``onehot[e, t] = (target_local[e] == t)`` with iota +
    compare (a VPU op), and accumulates ``w[1, E_blk] @ onehot[E_blk, T]``
    into the tile's degree row — a [1, E] x [E, T] matmul on the MXU;
  * the degree row stays resident in VMEM across the edge-block grid
    dimension (output BlockSpec index ignores it), so HBM sees each degree
    tile exactly once.

Grid: (n_tiles, n_edge_blocks).  VMEM per step: E_blk ints + E_blk floats +
E_blk x T onehot + 8 x T accumulator — for the default (E_blk=512, T=1024)
that is ~2.2 MB, comfortably inside the ~16 MB less double-buffering budget.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _degree_kernel(tl_ref, w_ref, out_ref):
    """One (tile, edge-block) grid step.

    tl_ref:  int32[1, 1, E_blk]   target ids local to this tile (-1 = padding)
    w_ref:   float32[1, 1, E_blk] current alive-weight of each slot (0 = dead)
    out_ref: float32[1, 8, T]     this tile's degree row (8 sublanes for MXU)
    """
    eb = pl.program_id(1)

    @pl.when(eb == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    tl = tl_ref[0, 0, :]
    w = w_ref[0, 0, :]
    t = out_ref.shape[2]
    # one-hot via iota compare; padding (-1) matches no column.
    cols = jax.lax.broadcasted_iota(jnp.int32, (tl.shape[0], t), 1)
    onehot = (tl[:, None] == cols).astype(jnp.float32)
    # [1, E_blk] @ [E_blk, T] on the MXU.
    partial = jnp.dot(
        w[None, :], onehot, preferred_element_type=jnp.float32
    )  # [1, T]
    out_ref[0, 0:1, :] += partial


@functools.partial(
    jax.jit, static_argnames=("tile_size", "block_e", "interpret")
)
def tiled_degrees_pallas(
    target_local: jax.Array,  # int32[n_tiles, max_epT]
    w: jax.Array,  # float32[n_tiles, max_epT] per-slot alive weight
    *,
    tile_size: int,
    block_e: int = 512,
    interpret: bool | None = None,  # None: compiled on TPU, interpreter elsewhere
) -> jax.Array:
    """Returns float32[n_tiles, tile_size] degree histogram."""
    from repro.kernels import resolve_interpret

    interpret = resolve_interpret(interpret)
    n_tiles, max_epT = target_local.shape
    assert max_epT % block_e == 0, (max_epT, block_e)
    n_eb = max_epT // block_e

    # A unit middle axis keeps each block's last two dims at (1, block_e):
    # the TPU lowering needs them equal to the array's or (8, 128)-aligned,
    # and a (1, block_e) block of the 2-D array is neither.
    out = pl.pallas_call(
        _degree_kernel,
        grid=(n_tiles, n_eb),
        in_specs=[
            pl.BlockSpec((1, 1, block_e), lambda t, e: (t, 0, e)),
            pl.BlockSpec((1, 1, block_e), lambda t, e: (t, 0, e)),
        ],
        out_specs=pl.BlockSpec((1, 8, tile_size), lambda t, e: (t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_tiles, 8, tile_size), jnp.float32),
        interpret=interpret,
    )(target_local[:, None, :], w[:, None, :])
    return out[:, 0, :]
