"""Builds one dry-run cell: (jit-able step fn, abstract sharded inputs) for
an (architecture x input-shape x mesh) combination.

Everything is ShapeDtypeStruct-based — no device allocation; ``.lower()`` +
``.compile()`` on the result is the multi-pod dry-run.  The same builder
drives the roofline analyzer and the perf variants (``overrides``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Mapping, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchSpec, ShapeSpec
from repro.configs.registry import get_arch
from repro.data.synthetic import abstract_batch
from repro.launch import tables
from repro.launch.mesh import all_axes, make_production_mesh
from repro.optim import AdamWConfig, abstract_state
from repro.optim.adamw import Quantized
from repro.sharding.rules import (
    AxisRules,
    pspecs_for_params,
    sharding_ctx,
)
from repro.train.step import (
    make_loss_fn,
    make_lm_prefill,
    make_recsys_retrieval,
    make_recsys_serve,
    make_train_step,
    specialize_gnn_config,
)

# Per-arch optimizer-state dtype: int8 block-quantized Adam moments are what
# let the 774B-param llama4 cell approach 16 GB/chip (8-bit-Adam, DESIGN §4).
_MOMENT_DTYPE = {
    "llama4-maverick-400b-a17b": "int8",
    "qwen2-72b": "fp32",
}


class Cell(NamedTuple):
    arch_id: str
    shape_name: str
    spec: ArchSpec
    shape: ShapeSpec
    mesh: Mesh
    rules: AxisRules
    fn: Callable
    args: Tuple[Any, ...]
    # Static metadata for the roofline report.
    info: Dict[str, Any]


class SkipCell(Exception):
    pass


def _named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def _sanitize_pspec(shape, spec: P, mesh: Mesh) -> P:
    """Drops sharding from dims the mesh axes don't divide evenly: explicit
    INPUT shardings must tile exactly (GSPMD pads only intermediates).
    Tiny GNN weights like (1433, 64) or heads (128, 7) fall back toward
    replication dim by dim."""
    out = []
    for i, ax in enumerate(tuple(spec) + (None,) * (len(shape) - len(tuple(spec)))):
        if ax is None:
            out.append(None)
            continue
        axs = (ax,) if isinstance(ax, str) else tuple(ax)
        total = 1
        for a in axs:
            total *= mesh.shape[a]
        if shape[i] % total == 0:
            out.append(ax)
        else:
            # try a prefix of the axes (e.g. ('pod','data') -> ('pod',))
            kept = []
            run = 1
            for a in axs:
                if shape[i] % (run * mesh.shape[a]) == 0:
                    kept.append(a)
                    run *= mesh.shape[a]
                else:
                    break
            out.append(tuple(kept) if kept else None)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def _attach(sds_tree, pspec_tree, mesh: Mesh):
    """Rebuild ShapeDtypeStructs with (divisibility-sanitized) shardings."""
    return jax.tree.map(
        lambda s, p: jax.ShapeDtypeStruct(
            s.shape, s.dtype,
            sharding=_named(mesh, _sanitize_pspec(s.shape, p, mesh)),
        ),
        sds_tree,
        pspec_tree,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )


def _batch_pspecs(batch_sds, axes_map, rules: AxisRules):
    def one(key_path, leaf):
        key = key_path[-1].key if hasattr(key_path[-1], "key") else str(key_path[-1])
        axes = axes_map.get(key)
        if axes is None:
            return P()
        axes = tuple(axes)[: leaf.ndim] + (None,) * max(0, leaf.ndim - len(axes))
        return rules.pspec(axes)

    return jax.tree_util.tree_map_with_path(
        one, batch_sds, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct)
    )


def _opt_pspecs(opt_abstract, param_pspecs, mesh: Mesh, rules: AxisRules):
    """Moments inherit the parameter sharding; int8 Quantized payloads are
    rank-changed (blocked), so they shard dim0 over all axes when divisible."""
    flat_all = tuple(mesh.axis_names)
    n_dev = int(np.prod(list(mesh.shape.values())))
    flat_params_spec = jax.tree.leaves(
        param_pspecs, is_leaf=lambda x: isinstance(x, P)
    )

    def mirror(tree):
        leaves, tdef = jax.tree.flatten(
            tree, is_leaf=lambda x: isinstance(x, Quantized)
        )
        out = []
        for leaf, ps in zip(leaves, flat_params_spec):
            if isinstance(leaf, Quantized):
                nb = leaf.q.shape[0]
                sp = P(flat_all) if nb % n_dev == 0 else P()
                out.append(Quantized(q=sp, scale=sp, shape=leaf.shape))
            else:
                out.append(ps)
        return tdef.unflatten(out)

    from repro.optim.adamw import AdamWState

    return AdamWState(
        step=P(), mu=mirror(opt_abstract.mu), nu=mirror(opt_abstract.nu)
    )


def _attach_opt(opt_abstract, opt_pspecs, mesh):
    def go(sds, spec):
        if isinstance(sds, jax.ShapeDtypeStruct):
            return jax.ShapeDtypeStruct(
                sds.shape, sds.dtype,
                sharding=_named(mesh, _sanitize_pspec(sds.shape, spec, mesh)),
            )
        return sds

    return jax.tree.map(
        go, opt_abstract, opt_pspecs,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )


# ---------------------------------------------------------------------------
# Family builders
# ---------------------------------------------------------------------------


def _abstract_params(spec: ArchSpec, cfg) -> Any:
    from repro.train.step import init_model_params

    return jax.eval_shape(
        lambda k: init_model_params(spec, k, cfg=cfg), jax.random.PRNGKey(0)
    )


def _lm_cell(spec, shape, mesh, rules, overrides) -> Tuple[Callable, Tuple, Dict]:
    cfg = dataclasses.replace(
        spec.config,
        attn_impl=overrides.get("attn_impl", "auto"),
        remat=overrides.get("remat", True),
        q_chunk=overrides.get("q_chunk", spec.config.q_chunk),
        kv_chunk=overrides.get("kv_chunk", spec.config.kv_chunk),
    )
    p = dict(shape.params)
    params = _abstract_params(spec, cfg)
    pspecs = pspecs_for_params(params, spec.param_rules, rules)
    params_sds = _attach(params, pspecs, mesh)
    axes_map = tables.input_axes(spec, shape)
    batch = abstract_batch(spec, shape)
    batch_sds = _attach(batch, _batch_pspecs(batch, axes_map, rules), mesh)
    info = {
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "tokens_per_step": p["global_batch"] * (p["seq_len"] if shape.kind == "train" else 1),
    }

    if shape.kind == "train":
        opt_cfg = AdamWConfig(
            moment_dtype=overrides.get(
                "moment_dtype", _MOMENT_DTYPE.get(spec.arch_id, "fp32")
            )
        )
        loss_fn = make_loss_fn(spec, shape.kind, cfg=cfg)
        step = make_train_step(loss_fn, opt_cfg)
        opt = abstract_state(params, opt_cfg)
        opt_sds = _attach_opt(opt, _opt_pspecs(opt, pspecs, mesh, rules), mesh)
        info["flops_model"] = 6 * cfg.active_param_count() * info["tokens_per_step"]
        return step, (params_sds, opt_sds, batch_sds), info

    if shape.kind == "prefill":
        step = make_lm_prefill(cfg)
        info["tokens_per_step"] = p["global_batch"] * p["seq_len"]
        info["flops_model"] = 2 * cfg.active_param_count() * info["tokens_per_step"]
        return step, (params_sds, batch_sds), info

    if shape.kind in ("decode", "decode_long"):
        from repro.models.transformer import cache_spec, decode_step

        b = p["global_batch"]
        spec_c = cache_spec(cfg, b, p["seq_len"])
        cache = spec_c.abstract()
        cache_pspec = {
            k: rules.pspec((None, "batch", "kv_seq", None, None))
            for k in cache
        }
        cache_sds = _attach(cache, cache_pspec, mesh)
        cur_len = jax.ShapeDtypeStruct((), jnp.int32, sharding=_named(mesh, P()))

        def step(params, cache, tokens, cur_len):
            logits, cache, cur_len = decode_step(params, cfg, cache, tokens, cur_len)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return {"next": nxt, "logits": logits}, cache, cur_len

        tokens = batch_sds["tokens"]
        info["flops_model"] = 2 * cfg.active_param_count() * b
        info["kv_cache_bytes"] = int(
            2 * np.prod([cfg.n_layers, b, spec_c.max_len, cfg.n_kv_heads, cfg.d_head])
            * 2
        )
        return step, (params_sds, cache_sds, tokens, cur_len), info

    raise ValueError(shape.kind)


def _gnn_flops_model(spec, cfg, shape) -> int:
    """Analytic 'useful' FLOPs: per-layer dense transforms x nodes (+edges),
    x3 for fwd+bwd.  Message passing adds O(E*d) adds counted at 2 flops."""
    p = dict(shape.params)
    d_h = cfg.d_hidden if hasattr(cfg, "d_hidden") else 128
    if shape.kind == "sampled_train" and spec.arch_id == "graphsage-reddit":
        r = p["batch_nodes"]
        f1, f2 = p["fanout"]
        n_eff = r * (1 + f1 + f1 * f2)
        e_eff = r * f1 + r * f1 * f2
    elif shape.kind == "molecule_train":
        n_eff = p["batch"] * p["n_nodes"]
        e_eff = p["batch"] * p["n_edges"]
    else:
        n_eff = p["n_nodes"]
        e_eff = p["n_edges"]
    d_in = p.get("d_feat", d_h)
    n_layers = getattr(cfg, "n_layers", 2)
    per_node = 2 * (d_in * d_h + (n_layers - 1) * d_h * d_h + d_h * d_h)
    per_edge = 2 * d_h * n_layers
    return 3 * (n_eff * per_node + e_eff * per_edge)


def _gnn_cell(spec, shape, mesh, rules, overrides):
    cfg = specialize_gnn_config(spec.config, dict(shape.params))
    params = _abstract_params(spec, cfg)
    pspecs = pspecs_for_params(params, spec.param_rules, rules)
    params_sds = _attach(params, pspecs, mesh)
    batch = abstract_batch(spec, shape)
    axes_map = tables.input_axes(spec, shape)
    batch_sds = _attach(batch, _batch_pspecs(batch, axes_map, rules), mesh)
    opt_cfg = AdamWConfig(moment_dtype=overrides.get("moment_dtype", "fp32"))
    loss_fn = make_loss_fn(spec, shape.kind, cfg=cfg)
    step = make_train_step(loss_fn, opt_cfg)
    opt = abstract_state(params, opt_cfg)
    opt_sds = _attach_opt(opt, _opt_pspecs(opt, pspecs, mesh, rules), mesh)
    info = {
        "params": int(
            sum(np.prod(l.shape) for l in jax.tree.leaves(params))
        ),
        "flops_model": _gnn_flops_model(spec, cfg, shape),
    }
    return step, (params_sds, opt_sds, batch_sds), info


def _recsys_cell(spec, shape, mesh, rules, overrides):
    cfg = spec.config
    params = _abstract_params(spec, cfg)
    pspecs = pspecs_for_params(params, spec.param_rules, rules)
    params_sds = _attach(params, pspecs, mesh)
    batch = abstract_batch(spec, shape)
    axes_map = tables.input_axes(spec, shape)
    batch_sds = _attach(batch, _batch_pspecs(batch, axes_map, rules), mesh)
    p = dict(shape.params)
    d = cfg.embed_dim
    tower = 0
    din = 2 * d
    for t_d in cfg.tower_dims:
        tower += din * t_d
        din = t_d
    item_tower = 0
    din = d
    for t_d in cfg.tower_dims:
        item_tower += din * t_d
        din = t_d
    info = {
        "params": int(sum(np.prod(l.shape) for l in jax.tree.leaves(params))),
    }
    if shape.kind == "train":
        opt_cfg = AdamWConfig(moment_dtype=overrides.get("moment_dtype", "fp32"))
        loss_fn = make_loss_fn(spec, shape.kind, cfg=cfg)
        step = make_train_step(loss_fn, opt_cfg)
        opt = abstract_state(params, opt_cfg)
        opt_sds = _attach_opt(opt, _opt_pspecs(opt, pspecs, mesh, rules), mesh)
        b = p["batch"]
        info["flops_model"] = 3 * (
            b * 2 * (tower + item_tower) + 2 * b * b * cfg.tower_dims[-1]
        )
        return step, (params_sds, opt_sds, batch_sds), info
    if shape.kind == "serve":
        step = make_recsys_serve(cfg)
        b = p["batch"]
        info["flops_model"] = b * 2 * (tower + item_tower)
        return step, (params_sds, batch_sds), info
    if shape.kind == "retrieval":
        step = make_recsys_retrieval(cfg, k=overrides.get("topk", 100))
        nc = p["n_candidates"]
        info["flops_model"] = 2 * tower + nc * 2 * item_tower + 2 * nc * cfg.tower_dims[-1]
        return step, (params_sds, batch_sds), info
    raise ValueError(shape.kind)


def _densest_cell(spec, shape, mesh, rules, overrides):
    from repro.core.api import Problem, default_solver

    p = dict(shape.params)
    n, m = p["n_nodes"], p["n_edges"]
    eps = overrides.get("eps", spec.config.eps)
    max_passes = overrides.get("max_passes", spec.config.max_passes)
    edge_axes = all_axes(mesh)
    n_shards = int(np.prod([mesh.shape[a] for a in edge_axes]))
    m_pad = ((m + n_shards - 1) // n_shards) * n_shards
    espec = rules.pspec(("edges",))
    batch = {
        "src": jax.ShapeDtypeStruct((m_pad,), jnp.int32, sharding=_named(mesh, espec)),
        "dst": jax.ShapeDtypeStruct((m_pad,), jnp.int32, sharding=_named(mesh, espec)),
        "weight": jax.ShapeDtypeStruct((m_pad,), jnp.float32, sharding=_named(mesh, espec)),
        "mask": jax.ShapeDtypeStruct((m_pad,), jnp.bool_, sharding=_named(mesh, espec)),
    }
    # Every branch below is the same PeelEngine loop (core/engine.py) under
    # shard_map, the front door's uncompacted mesh program; the override
    # picks the policy / degree backend combination.
    policy = overrides.get("policy", "undirected")
    wants_sketch = shape.kind == "peel_sketched" or overrides.get("use_sketch")
    if policy != "undirected" and wants_sketch:
        raise ValueError(
            f"policy={policy!r} has no distributed Count-Sketch cell yet; "
            "drop the sketch config or use policy='undirected'"
        )
    mesh_kw = dict(
        eps=eps, max_passes=max_passes, substrate="mesh",
        edge_axes=tuple(edge_axes),
    )
    if policy == "topk":
        problem = Problem.at_least_k(
            k=int(overrides.get("k", 2)), min_deg_fallback=False,
            ceil_count=True, **mesh_kw,
        )
    elif policy == "directed":
        problem = Problem.directed(**mesh_kw)
    elif wants_sketch:
        problem = Problem.undirected(
            backend="sketch",
            sketch_tables=p.get("t", overrides.get("t", 5)),
            sketch_buckets=p.get("b", overrides.get("b", 1 << 17)),
            **mesh_kw,
        )
    else:
        problem = Problem.undirected(
            wire_dtype=overrides.get("wire_dtype", "f32"), **mesh_kw
        )
    fn = default_solver.mesh_program(problem, mesh, n)
    c_arg = (float(overrides.get("c", 1.0)),) if policy == "directed" else ()

    def step(src, dst, weight, mask):
        return fn(src, dst, weight, mask, *c_arg)

    # Per-pass useful work: one weighted degree count (2 adds per endpoint
    # per edge) + threshold scan; expected passes ~ log_{1+eps} n.
    import math

    exp_passes = min(max_passes, math.ceil(math.log(max(n, 2)) / math.log1p(eps)))
    info = {
        "params": 0,
        "flops_model": exp_passes * (4 * m + 4 * n),
        "expected_passes": exp_passes,
    }
    return step, (batch["src"], batch["dst"], batch["weight"], batch["mask"]), info


_FAMILY_BUILDERS = {
    "lm": _lm_cell,
    "gnn": _gnn_cell,
    "recsys": _recsys_cell,
    "densest": _densest_cell,
}


def build_cell(
    arch_id: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    overrides: Optional[Mapping[str, Any]] = None,
    mesh: Optional[Mesh] = None,
) -> Cell:
    overrides = dict(overrides or {})
    spec = get_arch(arch_id)
    shape = spec.shape(shape_name)
    if shape.skip_reason is not None and not overrides.get("force", False):
        raise SkipCell(shape.skip_reason)
    if mesh is None:
        mesh = make_production_mesh(multi_pod=multi_pod)
    rules = tables.rules_for(
        spec, shape, multi_pod, extra=overrides.get("rules")
    )
    fn, args, info = _FAMILY_BUILDERS[spec.family](
        spec, shape, mesh, rules, overrides
    )
    info.update(
        mesh="x".join(f"{k}={v}" for k, v in mesh.shape.items()),
        n_devices=int(np.prod(list(mesh.shape.values()))),
        family=spec.family,
        kind=shape.kind,
    )
    return Cell(
        arch_id=arch_id, shape_name=shape_name, spec=spec, shape=shape,
        mesh=mesh, rules=rules, fn=fn, args=args, info=info,
    )


def lower_cell(cell: Cell):
    """Traces + lowers the cell under the ambient sharding context."""
    with sharding_ctx(cell.mesh, cell.rules):
        return jax.jit(cell.fn).lower(*cell.args)
