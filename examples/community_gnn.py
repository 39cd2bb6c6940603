"""Community mining -> GNN training: the paper's community application
feeding the framework's training stack end-to-end.

    PYTHONPATH=src python examples/community_gnn.py

1. Iteratively peels node-disjoint dense communities out of a planted-
   partition graph (the paper's §6 enumeration note).
2. Uses community membership as (noisy) node labels and trains GraphSAGE
   with the real layered neighbor sampler, the fault-tolerant Trainer and
   async checkpointing — then restarts from the checkpoint to show the
   resume path.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Problem, solve
from repro.graph.edgelist import from_numpy
from repro.graph.generators import planted_partition
from repro.graph.sampler import CSRGraph, LayeredSampler


def peel_communities(edges, k_communities: int, eps: float = 0.5):
    """Node-disjoint (approx) densest subgraphs, greedily removed."""
    src = np.asarray(edges.src)
    dst = np.asarray(edges.dst)
    n = edges.n_nodes
    removed = np.zeros(n, bool)
    communities = []
    for _ in range(k_communities):
        keep = ~(removed[src] | removed[dst])
        sub = from_numpy(src[keep], dst[keep], n)
        res = solve(
            sub, Problem.undirected(eps=eps, track_history=True, compaction="off")
        )
        nodes, rho = res.nodes(), float(res.best_density)
        nodes = np.asarray([u for u in nodes if not removed[u]])
        if len(nodes) == 0:
            break
        communities.append((nodes, rho))
        removed[nodes] = True
    return communities


def main():
    n, k = 3000, 4
    # Heterogeneous densities: the peel extracts communities densest-first
    # (with uniform p_in the UNION has the same density as each block and
    # the algorithm correctly returns the whole graph).
    edges, truth = planted_partition(
        n=n, k=k, p_in=(0.20, 0.12, 0.08, 0.05), p_out=0.0005, seed=11
    )
    print(f"graph: n={n} m={int(edges.num_real_edges())}, {k} planted communities")

    comms = peel_communities(edges, k)
    labels = np.full(n, k, np.int32)  # background class k
    for ci, (nodes, rho) in enumerate(comms):
        labels[nodes] = ci
        purity = np.bincount(truth[nodes], minlength=k).max() / len(nodes)
        print(f"community {ci}: |S|={len(nodes):4d} rho={rho:6.2f} purity={purity:.0%}")

    # ---- GraphSAGE on the mined labels ------------------------------------
    import dataclasses

    from repro.configs import get_arch
    from repro.optim import AdamWConfig, apply_updates, init_state
    from repro.train.step import init_model_params, make_loss_fn, specialize_gnn_config
    from repro.train.trainer import Trainer, TrainerConfig

    spec = get_arch("graphsage-reddit")
    cfg = specialize_gnn_config(
        spec.reduced_config, dict(d_feat=16, n_classes=k + 1)
    )
    g = CSRGraph.from_edges(np.asarray(edges.src), np.asarray(edges.dst), n)
    rng = np.random.default_rng(0)
    feats = rng.standard_normal((n, 16)).astype(np.float32)
    feats[:, 0] = labels == 0  # weakly informative features
    feats_j = jnp.asarray(feats)
    sampler = LayeredSampler(g, labels, batch_nodes=64, fanout=(5, 3), seed=1)

    class SamplerStream:
        def __init__(self, s):
            self.s = s

        def __next__(self):
            b = next(self.s)
            return {
                "feat_table": feats_j,
                **{kk: jnp.asarray(v) for kk, v in b.items()},
            }

        def checkpoint_state(self):
            return self.s.checkpoint_state()

        def restore(self, st):
            self.s.restore(st)

    opt_cfg = AdamWConfig(lr=3e-3, weight_decay=0.0)
    loss_fn = make_loss_fn(spec, "sampled_train", cfg=cfg)

    @jax.jit
    def step_fn(state, batch):
        params, opt = state
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch=batch
        )
        params, opt, om = apply_updates(params, grads, opt, opt_cfg)
        return (params, opt), {**metrics, **om}

    params = init_model_params(spec, jax.random.PRNGKey(0), cfg=cfg)
    import shutil

    shutil.rmtree("experiments/community_gnn_ckpt", ignore_errors=True)
    tcfg = TrainerConfig(
        total_steps=150, ckpt_dir="experiments/community_gnn_ckpt", ckpt_every=50,
    )
    tr = Trainer(tcfg, step_fn, (params, init_state(params, opt_cfg)),
                 SamplerStream(sampler))
    t0 = time.time()
    out = tr.run()
    first = tr.metrics_log[0]["loss"]
    print(
        f"\nGraphSAGE on mined communities: loss {first:.3f} -> "
        f"{out['loss']:.3f} in {out['step']} steps ({time.time() - t0:.0f}s)"
    )

    # resume path: restart and train 50 more steps from the checkpoint
    tr2 = Trainer(
        dataclasses.replace(tcfg, total_steps=200), step_fn,
        (params, init_state(params, opt_cfg)), SamplerStream(sampler),
    )
    assert tr2.try_restore() and tr2.step == 150
    out2 = tr2.run()
    print(f"resumed at 150 -> {out2['step']}: loss {out2['loss']:.3f}")


if __name__ == "__main__":
    main()
