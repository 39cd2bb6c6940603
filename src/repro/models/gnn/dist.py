"""Tile-partitioned message passing: the paper's MapReduce edge partitioning
as a differentiable GNN training primitive.

GSPMD's default lowering of `segment_sum(X[src] * w, dst)` with randomly
sharded edges produces FULL-node-state partial sums on every device followed
by an all-reduce — O(N · width) wire bytes per device per layer (the
equiformer x ogb_products §Perf bottleneck).  This module co-partitions
edges with their DESTINATION node tile (the 'shuffle done once' of
graph/partition.py / paper §5.2), so inside ``shard_map``:

  forward:   all-gather X (one ring AG of the node state)
             -> gather/scale local in-edges -> LOCAL segment_sum.  No psum.
  backward:  dX needs edges grouped by SOURCE -> a second static tiling of
             the same edges; one ring AG of dZbar, local scatter.  dw is
             computed on the in-tiling where dZbar is already local.

Wire bytes per layer drop from 2·|X|·(g-1)/g (AR of f32 partials) to
|X|·(g-1)/g bf16 each way — measured 3.3x on the ogb_products shape (see
EXPERIMENTS.md §Perf, equiformer iteration 3).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class EdgeTiling:
    """Static two-way tiling of a directed edge list over D devices.

    Node tile d owns rows [d*tile_n, (d+1)*tile_n).  ``in_*`` buckets edges
    by dst tile (forward), ``out_*`` by src tile (backward); both padded to
    the max per-tile count (mask via w=0 slots handled by the caller's
    weights; padding slots point at local row 0 with weight 0).
    """

    in_src: np.ndarray  # int32[D, E_in]  global src ids
    in_dst_local: np.ndarray  # int32[D, E_in]  dst - tile_start
    in_eid: np.ndarray  # int32[D, E_in]  original edge index (-1 pad)
    out_dst: np.ndarray  # int32[D, E_out] global dst ids
    out_src_local: np.ndarray  # int32[D, E_out]
    out_eid: np.ndarray  # int32[D, E_out]
    tile_n: int
    n_nodes_padded: int


def build_edge_tiling(
    src: np.ndarray, dst: np.ndarray, n_nodes: int, n_devices: int,
    pad_multiple: int = 8,
) -> EdgeTiling:
    n_pad = ((n_nodes + n_devices - 1) // n_devices) * n_devices
    tile_n = n_pad // n_devices

    def bucket(key: np.ndarray, other: np.ndarray):
        tile = key // tile_n
        order = np.argsort(tile, kind="stable")
        key_s, other_s, eid_s = key[order], other[order], order
        counts = np.bincount(tile, minlength=n_devices)
        width = int(counts.max(initial=0))
        width = max(((width + pad_multiple - 1) // pad_multiple) * pad_multiple,
                    pad_multiple)
        loc = np.zeros((n_devices, width), np.int32)
        oth = np.zeros((n_devices, width), np.int32)
        eid = np.full((n_devices, width), -1, np.int32)
        starts = np.concatenate([[0], np.cumsum(counts)])
        for d in range(n_devices):
            s, c = starts[d], counts[d]
            loc[d, :c] = (key_s[s : s + c] - d * tile_n).astype(np.int32)
            oth[d, :c] = other_s[s : s + c].astype(np.int32)
            eid[d, :c] = eid_s[s : s + c].astype(np.int32)
        return loc, oth, eid

    in_dst_local, in_src, in_eid = bucket(np.asarray(dst, np.int64),
                                          np.asarray(src, np.int64))
    out_src_local, out_dst, out_eid = bucket(np.asarray(src, np.int64),
                                             np.asarray(dst, np.int64))
    return EdgeTiling(
        in_src=in_src, in_dst_local=in_dst_local, in_eid=in_eid,
        out_dst=out_dst, out_src_local=out_src_local, out_eid=out_eid,
        tile_n=tile_n, n_nodes_padded=n_pad,
    )


def make_tiled_neighbor_sum(tiling: EdgeTiling, mesh: Mesh, axes: Tuple[str, ...]):
    """Returns ``f(X, w_edge) -> Z`` with Z[n] = sum_{e: dst=n} w_e X[src_e].

    X: [N_pad, ...] node features sharded over ``axes`` on dim 0;
    w_edge: float[E] per-ORIGINAL-edge differentiable weights (replicated).
    Z has X's shape/sharding.  Gradients flow to both X and w_edge.
    """
    spec_x = P(axes)
    spec_r = P()
    in_src = jnp.asarray(tiling.in_src)
    in_dst = jnp.asarray(tiling.in_dst_local)
    in_eid = jnp.asarray(tiling.in_eid)
    out_dst = jnp.asarray(tiling.out_dst)
    out_src = jnp.asarray(tiling.out_src_local)
    out_eid = jnp.asarray(tiling.out_eid)
    tile_n = tiling.tile_n
    n_edges_sig = None  # closed over at call time

    def _w_slot(w_edge, eid):
        safe = jnp.maximum(eid, 0)
        return jnp.where(eid >= 0, w_edge[safe], 0.0)

    def fwd_local(x_local, w_edge, src_g, dst_l, eid):
        # [1, E] leading shard dim from shard_map on the tiling arrays.
        src_g, dst_l, eid = src_g[0], dst_l[0], eid[0]
        xg = jax.lax.all_gather(x_local, axes, axis=0, tiled=True)  # [N, ...]
        w = _w_slot(w_edge, eid)
        msgs = xg[src_g] * w.reshape((-1,) + (1,) * (xg.ndim - 1))
        return jax.ops.segment_sum(msgs, dst_l, num_segments=tile_n)

    def bwd_x_local(dz_local, w_edge, dst_g, src_l, eid):
        dst_g, src_l, eid = dst_g[0], src_l[0], eid[0]
        dzg = jax.lax.all_gather(dz_local, axes, axis=0, tiled=True)
        w = _w_slot(w_edge, eid)
        msgs = dzg[dst_g] * w.reshape((-1,) + (1,) * (dzg.ndim - 1))
        return jax.ops.segment_sum(msgs, src_l, num_segments=tile_n)

    def bwd_w_local(x_local, dz_local, src_g, dst_l, eid, n_edges):
        # dw_e = <X[src_e], dZ[dst_e]>; dst is LOCAL in the in-tiling.
        src_g, dst_l, eid = src_g[0], dst_l[0], eid[0]
        xg = jax.lax.all_gather(x_local, axes, axis=0, tiled=True)
        contrib = jnp.sum(
            (xg[src_g] * dz_local[dst_l]).reshape(src_g.shape[0], -1), axis=-1
        )
        safe = jnp.maximum(eid, 0)
        dw_partial = jnp.zeros((n_edges,), contrib.dtype).at[safe].add(
            jnp.where(eid >= 0, contrib, 0.0)
        )
        return jax.lax.psum(dw_partial, axes)  # edges live on one tile each

    sm = partial(shard_map, mesh=mesh, check_vma=False)

    @jax.custom_vjp
    def f(x, w_edge):
        return sm(
            fwd_local,
            in_specs=(spec_x, spec_r, spec_x, spec_x, spec_x),
            out_specs=spec_x,
        )(x, w_edge, in_src, in_dst, in_eid)

    def f_fwd(x, w_edge):
        return f(x, w_edge), (x, w_edge)

    def f_bwd(res, dz):
        x, w_edge = res
        dx = sm(
            bwd_x_local,
            in_specs=(spec_x, spec_r, spec_x, spec_x, spec_x),
            out_specs=spec_x,
        )(dz, w_edge, out_dst, out_src, out_eid)
        dw = sm(
            partial(bwd_w_local, n_edges=w_edge.shape[0]),
            in_specs=(spec_x, spec_x, spec_x, spec_x, spec_x),
            out_specs=spec_r,
        )(x, dz, in_src, in_dst, in_eid)
        return dx.astype(x.dtype), dw.astype(w_edge.dtype)

    f.defvjp(f_fwd, f_bwd)
    return f


def neighbor_sum_reference(x, w_edge, src, dst, n_nodes):
    """GSPMD-default oracle: gather -> scale -> segment_sum."""
    msgs = x[src] * w_edge.reshape((-1,) + (1,) * (x.ndim - 1))
    return jax.ops.segment_sum(msgs, dst, num_segments=n_nodes)
