"""Chung-Lu power-law graphs made on the device from a seed.

The degree law is that of ``repro.graph.generators.chung_lu_power_law``:
node ``i`` (0-based) has expected-degree weight ``(i + 1) ** (-1 / (exponent
- 1))``, endpoint pairs are drawn independently with probability
proportional to those weights, self-loops and duplicate pairs are dropped,
and a uniform subset of exactly ``m`` distinct undirected edges is kept.
Edges come out as ``src < dst``, sorted by ``(src, dst)``.

Everything after the two small sampling tables runs as one jitted program,
so a Table 1 graph (68.9M edges) costs seconds instead of the host
generator's two minutes.

Sampling is by inverse CDF in two levels, both in float32: first a block
of ``BLOCK`` consecutive node ids, by the cumulative mass of the blocks,
then a node inside the block by its block-normalised cumulative mass.  A
single float32 CDF over millions of nodes would lose the light tail
(its steps fall below float32 resolution near 1); per block they do not.

How many pairs to draw is worked out from the configuration alone
(:func:`draws_for`): the expected number of distinct pairs is computed on
groups of nodes of near-equal weight and the draw count is raised until it
clears ``m`` with a margin.  A seed that still falls short fails loudly.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 2048  # node ids per first-level block (a power of two)
_MARGIN = 1.003  # expected distinct pairs over m


def node_probabilities(n: int, exponent: float) -> np.ndarray:
    """float64 endpoint probabilities of the Chung-Lu degree law."""
    w = np.arange(1, n + 1, dtype=np.float64) ** (-1.0 / (exponent - 1.0))
    return w / w.sum()


def _weight_groups(p: np.ndarray, single: int = 512, ratio: float = 1.05):
    """(count, mean probability) of node groups: the heaviest ``single``
    nodes alone, then runs of ids growing by ``ratio``."""
    n = len(p)
    bounds = list(range(min(single, n) + 1))
    b = bounds[-1]
    while b < n:
        b = min(max(b + 1, int(b * ratio)), n)
        bounds.append(b)
    bounds = np.asarray(bounds)
    cs = np.concatenate([[0.0], np.cumsum(p)])
    count = np.diff(bounds).astype(np.float64)
    return count, (cs[bounds[1:]] - cs[bounds[:-1]]) / count


def expected_distinct(draws: float, count: np.ndarray, pmean: np.ndarray) -> float:
    """Expected distinct undirected non-loop pairs among ``draws`` pairs."""
    q = 2.0 * np.outer(pmean, pmean)  # one unordered pair's chance per draw
    hit = -np.expm1(-draws * q)
    pairs = np.outer(count, count) * hit
    return float((pairs.sum() - (count * hit.diagonal()).sum()) / 2.0)


def draws_for(n: int, m: int, exponent: float) -> int:
    """Pairs to draw so that at least ``m`` distinct edges survive."""
    count, pmean = _weight_groups(node_probabilities(n, exponent))
    lo, hi = float(m), float(m)
    while expected_distinct(hi, count, pmean) < m * _MARGIN:
        lo, hi = hi, hi * 1.5
    for _ in range(40):
        mid = (lo + hi) / 2.0
        if expected_distinct(mid, count, pmean) >= m * _MARGIN:
            hi = mid
        else:
            lo = mid
    return int(np.ceil(hi))


def sampling_tables(n: int, exponent: float):
    """(block_cdf float32[n_blocks], local_cdf float32[n_blocks * BLOCK])."""
    p = node_probabilities(n, exponent)
    n_blocks = -(-n // BLOCK)
    padded = np.zeros(n_blocks * BLOCK, np.float64)
    padded[:n] = p
    per_block = padded.reshape(n_blocks, BLOCK)
    mass = per_block.sum(axis=1)
    block_cdf = np.cumsum(mass) / mass.sum()
    block_cdf[-1] = 1.0
    local = np.cumsum(per_block, axis=1) / mass[:, None]
    local[:, -1] = 1.0
    return block_cdf.astype(np.float32), local.reshape(-1).astype(np.float32)


def _endpoints(key, block_cdf, local_cdf, draws: int, n: int):
    kb, kl = jax.random.split(key)
    ub = jax.random.uniform(kb, (draws,), jnp.float32)
    ul = jax.random.uniform(kl, (draws,), jnp.float32)
    block = jnp.searchsorted(block_cdf, ub, side="right").astype(jnp.int32)
    block = jnp.minimum(block, block_cdf.shape[0] - 1)
    base = block * BLOCK
    # Count the block's CDF entries <= ul: that is the node's offset.
    off = jnp.zeros((draws,), jnp.int32)
    step = BLOCK // 2
    while step >= 1:
        take = local_cdf[base + off + step - 1] <= ul
        off = jnp.where(take, off + step, off)
        step //= 2
    return jnp.minimum(base + off, n - 1)


@partial(jax.jit, static_argnames=("n", "m", "draws"))
def _generate(key, block_cdf, local_cdf, *, n: int, m: int, draws: int):
    ka, kb, kp = jax.random.split(key, 3)
    a = _endpoints(ka, block_cdf, local_cdf, draws, n)
    b = _endpoints(kb, block_cdf, local_cdf, draws, n)
    lo, hi = jax.lax.sort((jnp.minimum(a, b), jnp.maximum(a, b)), num_keys=2)
    first = jnp.concatenate(
        [jnp.ones((1,), bool), (lo[1:] != lo[:-1]) | (hi[1:] != hi[:-1])]
    )
    valid = first & (lo != hi)
    distinct = jnp.sum(valid.astype(jnp.int32))
    # A uniform m-subset of the distinct pairs: those with the m smallest
    # random keys (ties go to the earlier pair).  Their positions, sorted,
    # keep the (lo, hi) order.  Single-operand sorts and gathers: the TPU
    # compiler takes minutes over sorts that carry several operands.
    pri = jax.random.bits(kp, (draws,), jnp.uint32) >> 1
    pri = jnp.where(valid, pri, jnp.uint32(0xFFFFFFFF))
    keep = jnp.sort(jnp.argsort(pri, stable=True)[:m])
    return lo[keep], hi[keep], distinct


def seed_key(seed: int):
    """A PRNG key from any whole number (JAX keeps only 32 bits of it)."""
    s = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.key(s & 0xFFFFFFFF), s >> 32)


def generate(n: int, m: int, exponent: float, seed: int):
    """``(src, dst)`` int32 device arrays of exactly ``m`` distinct edges."""
    block_cdf, local_cdf = sampling_tables(n, exponent)
    draws = draws_for(n, m, exponent)
    src, dst, distinct = _generate(
        seed_key(seed), jnp.asarray(block_cdf), jnp.asarray(local_cdf),
        n=n, m=m, draws=draws,
    )
    if int(distinct) < m:
        raise RuntimeError(
            f"seed {seed}: {int(distinct)} distinct pairs in {draws} draws, "
            f"fewer than m={m}"
        )
    return src, dst
