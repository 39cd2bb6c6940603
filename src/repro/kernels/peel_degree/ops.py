"""jit'd public wrapper: degrees of the current alive subgraph from the
static tile bucketing.  Wrapped in an engine ``FnBackend`` (by
``Problem(backend='pallas')``'s lowering, or :func:`degree_backend_from_tiling`),
the Pallas kernel powers the same Algorithm 1 loop the XLA path uses."""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.graph.edgelist import EdgeList
from repro.graph.partition import TiledEdges, bucket_edges_by_tile
from repro.kernels import resolve_interpret
from repro.kernels.peel_degree.kernel import tiled_degrees_pallas
from repro.kernels.peel_degree.ref import degrees_from_tiled, tiled_degrees_ref


@partial(jax.jit, static_argnames=("tile_size", "n_nodes", "use_pallas", "interpret"))
def tiled_degrees(
    target_local: jax.Array,  # int32[n_tiles, max_epT]
    edge_index: jax.Array,  # int32[n_tiles, max_epT], -1 padding
    w_alive: jax.Array,  # float32[E] per-ORIGINAL-edge alive weight
    *,
    tile_size: int,
    n_nodes: int,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """float32[n_nodes] degrees of the alive subgraph."""
    interpret = resolve_interpret(interpret)
    # Route each slot's current weight through the static bucketing.
    safe_idx = jnp.maximum(edge_index, 0)
    w = jnp.where(edge_index >= 0, w_alive[safe_idx], 0.0)
    if use_pallas:
        max_epT = target_local.shape[1]
        block_e = next(
            b for b in (512, 256, 128, 64, max_epT) if max_epT % b == 0
        )
        deg_tiles = tiled_degrees_pallas(
            target_local, w, tile_size=tile_size, block_e=block_e,
            interpret=interpret,
        )
    else:
        deg_tiles = tiled_degrees_ref(target_local, w, tile_size=tile_size)
    return degrees_from_tiled(deg_tiles, n_nodes)


def degree_fn_from_tiling(
    tiled: TiledEdges,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
):
    """Builds the degree function ``fn(edges, w_alive) -> deg[N]`` of one
    bucketed graph (the function an engine ``FnBackend`` wraps)."""
    tl = jnp.asarray(tiled.target_local)
    ei = jnp.asarray(tiled.edge_index)

    def fn(edges: EdgeList, w_alive: jax.Array) -> jax.Array:
        return tiled_degrees(
            tl, ei, w_alive,
            tile_size=tiled.tile_size, n_nodes=tiled.n_nodes,
            use_pallas=use_pallas, interpret=interpret,
        )

    return fn


def degree_backend_from_tiling(
    tiled: TiledEdges,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
):
    """Engine ``DegreeBackend`` wrapping the Pallas tiled-degree kernel, for
    use with :func:`repro.core.engine.run_peel` (undirected policies)."""
    from repro.core.engine import FnBackend

    return FnBackend(
        degree_fn_from_tiling(tiled, use_pallas=use_pallas, interpret=interpret)
    )


def tiling_for_edges(
    edges: EdgeList,
    tile_size: int = 1024,
    block: int = 512,
    pow2_pad: bool = False,
):
    """Buckets ALL edge slots (padding included): ``edge_index`` must address
    the original edge array because the per-pass ``w_alive`` is indexed over
    it, and padded slots already carry weight 0.  ``pow2_pad`` bounds the
    shape set across compaction rungs (see bucket_edges_by_tile)."""
    import numpy as np

    return bucket_edges_by_tile(
        np.asarray(edges.src), np.asarray(edges.dst),
        edges.n_nodes, tile_size=tile_size, block=block,
        directed=False, pow2_pad=pow2_pad,
    )
