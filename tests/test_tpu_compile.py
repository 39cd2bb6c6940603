"""Compile-only checks of the main-path Pallas kernels for a TPU v5e chip.

The TPU compiler ships with jaxlib and compiles for a chip that is only
described, so these tests need no accelerator: they catch what interpret
mode cannot (block shapes the TPU lowering refuses, VMEM overruns) at the
widths the chip path runs.  Nothing executes.  The topology is described
inside a module-scoped fixture (never at import time), so every test worker
collects the same tests and only the worker that runs this file loads the
TPU compiler.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# The 200k-node Chung-Lu graph of the chip smoke's Pallas phase (exponent
# 2.2, avg degree 8, seed 0, m = 745,862) buckets into 196 tiles of 1024
# nodes; the heaviest tile holds 441,344 endpoint slots (block 512).
_PEEL_TILES = 196
_PEEL_SLOTS = 441_344


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described chip's executables cannot be read back from a persistent
    # cache; keep these compiles out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def test_peel_degree_compiles_for_tpu(one_chip):
    from repro.kernels.peel_degree.kernel import tiled_degrees_pallas

    s = lambda dt: jax.ShapeDtypeStruct(
        (_PEEL_TILES, _PEEL_SLOTS), dt, sharding=one_chip
    )
    text = _compiled_text(
        lambda tl, w: tiled_degrees_pallas(
            tl, w, tile_size=1024, block_e=512, interpret=False
        ),
        s(jnp.int32),
        s(jnp.float32),
    )
    assert "tpu_custom_call" in text


def test_l0_sampler_compiles_for_tpu(one_chip):
    from repro.core.turnstile import TurnstileSketch
    from repro.kernels.l0_sampler.kernel import l0_delta_pallas

    # The geometry the turnstile runtime derives for sample_edges=16384,
    # updated by one 65,536-edge batch.
    p = TurnstileSketch(100_000, 16_384, use_pallas=False).params
    e = 1 << 16
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    u32 = jnp.uint32
    params = (p.a_lvl, p.c_lvl, p.a_fp, p.c_fp, p.a_cell, p.c_cell)
    text = _compiled_text(
        lambda u, v, sg, *hp: l0_delta_pallas(
            u, v, sg, *hp, n_levels=p.n_levels, n_cells=p.n_cells,
            interpret=False,
        ),
        *(s((e,), jnp.int32) for _ in range(3)),
        *(s(a.shape, u32) for a in params),
    )
    assert "tpu_custom_call" in text


def test_count_sketch_compiles_for_tpu(one_chip):
    from repro.configs.densest_mapreduce import SHAPES
    from repro.kernels.count_sketch.kernel import count_sketch_update_pallas

    dims = SHAPES["im_xl"].params
    t, b = dims["t"], dims["b"]
    e = 1 << 16
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    text = _compiled_text(
        lambda x, w, ah, ch, ag, cg: count_sketch_update_pallas(
            x, w, ah, ch, ag, cg, n_buckets=b, interpret=False
        ),
        s((e,), jnp.int32),
        s((e,), jnp.float32),
        *(s((t,), jnp.uint32) for _ in range(4)),
    )
    assert "tpu_custom_call" in text
