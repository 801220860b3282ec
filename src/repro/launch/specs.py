"""Per-cell (architecture x input-shape) build plans for the dry-run.

``build_cell(arch, shape, mesh)`` returns everything `jax.jit(...).lower()`
needs: the step function, argument ShapeDtypeStructs (no allocation — the
eval_shape pattern), and in/out shardings resolved from logical axis rules.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import DLRMConfig, GNNConfig, LMConfig, ShapeSpec, TaperSystemConfig
from repro.configs.registry import get_config, shapes_for
from repro.core.tpstry import synthetic_trie
from repro.core.visitor import _build_field_fn
from repro.distributed.sharding import LogicalAxisRules, rules_for
from repro.models import dlrm as dlrm_lib
from repro.models import transformer as tf
from repro.models.gnn import api as gnn_api
from repro.optim import AdamW

F32, BF16, I32, BOOL = jnp.float32, jnp.bfloat16, jnp.int32, jnp.bool_


def sds(shape, dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


@dataclass
class CellPlan:
    arch: str
    shape: ShapeSpec
    step_name: str
    step_fn: Callable
    args: Tuple[Any, ...]              # pytrees of ShapeDtypeStruct
    in_shardings: Tuple[Any, ...]
    out_shardings: Any
    meta: Dict[str, Any] = field(default_factory=dict)
    mesh: Any = None
    rules: Any = None
    constrain_activations: bool = True

    def lower(self):
        from repro.distributed.sharding import activation_sharding

        jitted = jax.jit(
            self.step_fn,
            in_shardings=self.in_shardings,
            out_shardings=self.out_shardings,
        )
        if self.constrain_activations and self.mesh is not None:
            with activation_sharding(self.mesh, self.rules):
                return jitted.lower(*self.args)
        return jitted.lower(*self.args)


def shape_init(init_fn, *args):
    """eval_shape an init that returns (params, logical): shapes without
    allocation, logical captured by side effect (it is static python)."""
    captured = {}

    def inner(rng):
        p, logical = init_fn(rng, *args)
        captured["logical"] = logical
        return p

    shapes = jax.eval_shape(inner, jax.random.PRNGKey(0))
    return shapes, captured["logical"]


def _shard_tree(mesh, rules, logical_tree, shapes_tree=None):
    from repro.distributed.sharding import tree_shardings

    return tree_shardings(mesh, logical_tree, shapes_tree, rules)


def _named(mesh, rules, *axes, shape=None):
    return NamedSharding(mesh, rules.spec(axes, shape, mesh))


# ---------------------------------------------------------------------------
# LM cells
# ---------------------------------------------------------------------------


def _lm_cell(cfg: LMConfig, shape: ShapeSpec, mesh, rules,
             optimizer: Optional[AdamW] = None, remat: bool = True,
             unroll: bool = False) -> CellPlan:
    B = shape.dim("global_batch")
    S = shape.dim("seq_len")
    params_shapes, logical = shape_init(tf.init, cfg)
    p_shard = _shard_tree(mesh, rules, logical, params_shapes)
    n_active = cfg.n_active_params()

    if shape.kind == "train":
        opt = optimizer or AdamW(learning_rate=3e-4)
        opt_shapes = jax.eval_shape(opt.init, params_shapes)
        opt_shard = _shard_tree(mesh, rules, opt.state_logical_axes(logical), opt_shapes)
        batch = {
            "tokens": sds((B, S), I32),
            "labels": sds((B, S), I32),
        }
        b_shard = {
            "tokens": _named(mesh, rules, "batch", None, shape=(B, S)),
            "labels": _named(mesh, rules, "batch", None, shape=(B, S)),
        }
        step = tf.make_train_step(cfg, opt, remat=remat, unroll=unroll)
        model_flops = 6.0 * n_active * B * S \
            + 12.0 * cfg.n_layers * cfg.n_heads * cfg.d_head * B * S * S / 2
        return CellPlan(
            cfg.name, shape, "train_step", step,
            (params_shapes, opt_shapes, batch),
            (p_shard, opt_shard, b_shard),
            (p_shard, opt_shard, None),
            {"model_flops": model_flops, "n_params": cfg.n_params(),
             "n_active": n_active, "tokens": B * S},
        )

    if shape.kind == "prefill":
        def prefill(params, tokens):
            logits, aux, cache = tf.forward(params, tokens, cfg,
                                            return_cache=True, unroll=unroll)
            return logits[:, -1, :], cache

        tokens = sds((B, S), I32)
        t_shard = _named(mesh, rules, "batch", None, shape=(B, S))
        cache_dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else F32
        cache_shapes = {
            "k": sds((cfg.n_layers, B, S, cfg.n_kv_heads, cfg.d_head), cache_dt),
            "v": sds((cfg.n_layers, B, S, cfg.n_kv_heads, cfg.d_head), cache_dt),
            "pos": sds((), I32),
        }
        cache_shard = _shard_tree(mesh, rules, tf.cache_logical_axes(cfg),
                                  cache_shapes)
        out_shard = (_named(mesh, rules, "batch", "vocab"), cache_shard)
        model_flops = 2.0 * n_active * B * S \
            + 4.0 * cfg.n_layers * cfg.n_heads * cfg.d_head * B * S * S / 2
        return CellPlan(
            cfg.name, shape, "prefill_step", prefill,
            (params_shapes, tokens), (p_shard, t_shard), out_shard,
            {"model_flops": model_flops, "n_params": cfg.n_params(),
             "n_active": n_active, "tokens": B * S},
        )

    # decode cells: one new token against a seq_len KV cache
    long_ctx = S >= 262144
    cache_logical = tf.cache_logical_axes(cfg, long_context=long_ctx)
    cache = {
        "k": sds((cfg.n_layers, B, S, cfg.n_kv_heads, cfg.d_head),
                 jnp.bfloat16 if cfg.dtype == "bfloat16" else F32),
        "v": sds((cfg.n_layers, B, S, cfg.n_kv_heads, cfg.d_head),
                 jnp.bfloat16 if cfg.dtype == "bfloat16" else F32),
        "pos": sds((), I32),
    }
    cache_shard = _shard_tree(mesh, rules, cache_logical, cache)
    tokens = sds((B, 1), I32)
    t_shard = _named(mesh, rules, None if long_ctx else "batch", None, shape=(B, 1))

    def decode(params, cache, tokens):
        return tf.decode_step(params, cache, tokens, cfg, unroll=unroll)

    # decode flops: params once per token + attention against the cache
    model_flops = 2.0 * n_active * B \
        + 4.0 * cfg.n_layers * cfg.n_heads * cfg.d_head * B * S
    kv_bytes = 2 * cfg.n_layers * B * S * cfg.n_kv_heads * cfg.d_head * 2
    return CellPlan(
        cfg.name, shape, "serve_step", decode,
        (params_shapes, cache, tokens),
        (p_shard, cache_shard, t_shard),
        ((_named(mesh, rules, None if long_ctx else "batch", None, "vocab"),
          cache_shard)),
        {"model_flops": model_flops, "n_params": cfg.n_params(),
         "n_active": n_active, "tokens": B, "kv_bytes": kv_bytes},
    )


# ---------------------------------------------------------------------------
# GNN cells
# ---------------------------------------------------------------------------


def _gnn_batch_specs(cfg: GNNConfig, shape: ShapeSpec, mesh, rules):
    d_feat = gnn_api.feature_dim(cfg, shape)
    if shape.name == "molecule":
        G = shape.dim("batch")
        N = G * shape.dim("n_nodes")
        E = G * shape.dim("n_edges")
    elif shape.name == "minibatch_lg":
        seeds = shape.dim("batch_nodes")
        f1, f2 = shape.dim("fanout1"), shape.dim("fanout2")
        N = seeds * (1 + f1 + f1 * f2)
        E = seeds * f1 + seeds * f1 * f2
    else:
        N = shape.dim("n_nodes")
        E = shape.dim("n_edges")
    batch = {
        "node_feat": sds((N, d_feat), F32),
        "edge_src": sds((E,), I32),
        "edge_dst": sds((E,), I32),
        "node_mask": sds((N,), BOOL),
        "edge_mask": sds((E,), BOOL),
    }
    shard = {
        "node_feat": _named(mesh, rules, "nodes", None, shape=(N, d_feat)),
        "edge_src": _named(mesh, rules, "edges", shape=(E,)),
        "edge_dst": _named(mesh, rules, "edges", shape=(E,)),
        "node_mask": _named(mesh, rules, "nodes", shape=(N,)),
        "edge_mask": _named(mesh, rules, "edges", shape=(E,)),
    }
    if gnn_api.needs_positions(cfg):
        batch["positions"] = sds((N, 3), F32)
        shard["positions"] = _named(mesh, rules, "nodes", None, shape=(N, 3))
    if shape.name == "molecule":
        batch["graph_id"] = sds((N,), I32)
        shard["graph_id"] = _named(mesh, rules, "nodes", shape=(N,))
    tshape, tdtype = gnn_api.target_spec(cfg, shape, N)
    batch["targets"] = sds(tshape, tdtype)
    shard["targets"] = _named(
        mesh, rules, "nodes" if tshape == (N,) else None, shape=tshape)
    return batch, shard, N, E, d_feat


def _gnn_model_flops(cfg: GNNConfig, N: int, E: int, d_feat: int) -> float:
    C, L = cfg.d_hidden, cfg.n_layers
    if cfg.kind == "gcn":
        dims = [d_feat] + [C] * (L - 1) + [cfg.n_classes]
        return sum(2.0 * N * a * b + 2.0 * E * a for a, b in zip(dims, dims[1:]))
    if cfg.kind == "gin":
        per = 2.0 * E * C + 2.0 * N * (C * C * 2)
        return L * per + 2.0 * N * d_feat * C
    S = (cfg.l_max + 1) ** 2
    if cfg.kind == "nequip":
        paths = (cfg.l_max + 1) ** 3  # upper bound on CG paths
        per = 2.0 * E * C * S * (2 * cfg.l_max + 1) * paths / (cfg.l_max + 1) \
            + 2.0 * N * C * C * S
        return L * per
    # equiformer_v2 (eSCN): rotation (S^1.5-ish) + per-m channel mixes
    wigner = sum((2 * l + 1) ** 2 for l in range(cfg.l_max + 1))
    per = 2.0 * E * C * wigner * 2 \
        + 2.0 * E * C * C * (2 * cfg.m_max + 1) \
        + 2.0 * N * C * C * 2
    return L * per


def _gnn_cell(cfg: GNNConfig, shape: ShapeSpec, mesh, rules,
              optimizer: Optional[AdamW] = None) -> CellPlan:
    batch, b_shard, N, E, d_feat = _gnn_batch_specs(cfg, shape, mesh, rules)
    params_shapes, logical = shape_init(gnn_api.init, cfg, shape)
    p_shard = _shard_tree(mesh, rules, logical, params_shapes)
    opt = optimizer or AdamW(learning_rate=1e-3, weight_decay=0.0)
    opt_shapes = jax.eval_shape(opt.init, params_shapes)
    opt_shard = _shard_tree(mesh, rules, opt.state_logical_axes(logical), opt_shapes)
    step = gnn_api.make_train_step(cfg, shape, opt)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params_shapes))
    return CellPlan(
        cfg.name, shape, "train_step", step,
        (params_shapes, opt_shapes, batch),
        (p_shard, opt_shard, b_shard),
        (p_shard, opt_shard, None),
        {"model_flops": _gnn_model_flops(cfg, N, E, d_feat),
         "n_params": n_params, "nodes": N, "edges": E},
    )


# ---------------------------------------------------------------------------
# DLRM cells
# ---------------------------------------------------------------------------


def _dlrm_cell(cfg: DLRMConfig, shape: ShapeSpec, mesh, rules,
               optimizer: Optional[AdamW] = None) -> CellPlan:
    params_shapes, logical = shape_init(dlrm_lib.init, cfg)
    p_shard = _shard_tree(mesh, rules, logical, params_shapes)
    mlp_flops = 0.0
    dims = (cfg.n_dense,) + cfg.bot_mlp
    mlp_flops += sum(2.0 * a * b for a, b in zip(dims, dims[1:]))
    n_feat = cfg.n_sparse + 1
    inter_in = n_feat * (n_feat - 1) // 2 + cfg.bot_mlp[-1]
    dims = (inter_in,) + cfg.top_mlp
    mlp_flops += sum(2.0 * a * b for a, b in zip(dims, dims[1:]))
    inter_flops = 2.0 * n_feat * n_feat * cfg.embed_dim

    if shape.kind == "retrieval":
        n_cand = shape.dim("n_candidates")
        query = {"dense": sds((1, cfg.n_dense), F32)}
        cands = sds((n_cand, cfg.bot_mlp[-1]), F32)

        def retrieve(params, query, candidates):
            return dlrm_lib.retrieval_step(params, query, candidates)

        return CellPlan(
            cfg.name, shape, "retrieval_step", retrieve,
            (params_shapes, query, cands),
            (p_shard, {"dense": _named(mesh, rules, None, None)},
             _named(mesh, rules, "candidates", None, shape=(n_cand, cfg.bot_mlp[-1]))),
            None,
            {"model_flops": 2.0 * n_cand * cfg.bot_mlp[-1],
             "n_params": cfg.n_params(), "batch": 1},
        )

    B = shape.dim("batch")
    batch = {
        "dense": sds((B, cfg.n_dense), F32),
        "sparse": sds((B, cfg.n_sparse), I32),
    }
    b_shard = {
        "dense": _named(mesh, rules, "batch", None, shape=(B, cfg.n_dense)),
        "sparse": _named(mesh, rules, "batch", None, shape=(B, cfg.n_sparse)),
    }
    per_ex_flops = mlp_flops + inter_flops
    lookup_bytes = B * cfg.n_sparse * cfg.embed_dim * 4

    if shape.kind == "serve":
        def serve(params, batch):
            return dlrm_lib.serve_step(params, batch, cfg)

        return CellPlan(
            cfg.name, shape, "serve_step", serve,
            (params_shapes, batch), (p_shard, b_shard),
            _named(mesh, rules, "batch"),
            {"model_flops": per_ex_flops * B, "n_params": cfg.n_params(),
             "batch": B, "lookup_bytes": lookup_bytes},
        )

    batch["labels"] = sds((B,), F32)
    b_shard["labels"] = _named(mesh, rules, "batch", shape=(B,))
    opt = optimizer or AdamW(learning_rate=1e-3, weight_decay=0.0)
    opt_shapes = jax.eval_shape(opt.init, params_shapes)
    opt_shard = _shard_tree(mesh, rules, opt.state_logical_axes(logical), opt_shapes)
    step = dlrm_lib.make_train_step(cfg, opt)
    return CellPlan(
        cfg.name, shape, "train_step", step,
        (params_shapes, opt_shapes, batch),
        (p_shard, opt_shard, b_shard),
        (p_shard, opt_shard, None),
        {"model_flops": 3.0 * per_ex_flops * B, "n_params": cfg.n_params(),
         "batch": B, "lookup_bytes": lookup_bytes},
    )


# ---------------------------------------------------------------------------
# TAPER refine-step cell (the paper's technique itself)
# ---------------------------------------------------------------------------


def _taper_cell(cfg: TaperSystemConfig, shape: ShapeSpec, mesh, rules,
                dense_ext_to: bool = False) -> CellPlan:
    n = shape.dim("n_vertices")
    m = shape.dim("n_edges")
    trie = synthetic_trie(cfg.n_labels, cfg.trie_depth, branching=2)
    k = cfg.k_partitions
    key = (trie.topology_signature(), k, trie.max_depth, n, m, dense_ext_to)
    fn = _build_field_fn(key, trie, k, trie.max_depth,
                         dense_ext_to=dense_ext_to)

    args = (
        sds((m,), I32), sds((m,), I32),                  # src, dst
        sds((n,), I32),                                  # labels
        sds((n, cfg.n_labels), I32),                     # cnt
        sds((cfg.n_labels,), I32),                       # label vertex counts
        sds((n,), I32),                                  # part
        sds((trie.n_nodes,), F32), sds((trie.n_nodes,), F32),  # p, cond_p
        # the destination order: src, dst and CSR rank of each edge in it,
        # in-degree and CSR prefixes
        sds((m,), I32), sds((m,), I32), sds((m,), I32),
        sds((n + 1,), I32), sds((n + 1,), I32),
    )
    e = _named(mesh, rules, "edges", shape=(m,))
    v = _named(mesh, rules, "nodes", shape=(n,))
    rep = NamedSharding(mesh, P())
    in_sh = (e, e, v, _named(mesh, rules, "nodes", None, shape=(n, cfg.n_labels)), rep, v, rep, rep,
             e, e, e, rep, rep)
    # no vertex has more than m edges
    kw = {"n": n, "m": m, "levels": max(m - 1, 0).bit_length()}

    def refine(*a):
        return fn(*a, **kw)

    # outputs: alpha (n,N), pr (n,), mass (m,), extro (n,), extroversion (n,)
    # [, ext_to (n, k)] — all sharded along their vertex/edge dim
    vN = _named(mesh, rules, "nodes", None, shape=(n, trie.n_nodes))
    vk = _named(mesh, rules, "nodes", None, shape=(n, k))
    out_sh = (vN, v, e, v, v) + ((vk,) if dense_ext_to else ())

    # DP flops: per depth>=2 trie node, one gather-multiply-scatter over edges
    steps = int((trie.depth >= 2).sum())
    model_flops = 4.0 * m * steps + 4.0 * m * trie.n_nodes
    return CellPlan(
        cfg.name, shape, "taper_refine_step", refine,
        args, in_sh, out_sh,
        {"model_flops": model_flops, "n_vertices": n, "n_edges": m,
         "trie_nodes": trie.n_nodes, "k": k},
    )


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def build_cell(arch: str, shape_name: str, mesh: Mesh,
               rules: Optional[LogicalAxisRules] = None,
               constrain_activations: bool = True, **kw) -> CellPlan:
    cfg = get_config(arch)
    rules = rules or rules_for(mesh)
    shape = next(s for s in shapes_for(arch) if s.name == shape_name)

    def pick(*names):
        return {k: v for k, v in kw.items() if k in names}

    if cfg.family == "lm":
        plan = _lm_cell(cfg, shape, mesh, rules,
                        **pick("optimizer", "remat", "unroll"))
    elif cfg.family == "gnn":
        plan = _gnn_cell(cfg, shape, mesh, rules, **pick("optimizer"))
    elif cfg.family == "recsys":
        plan = _dlrm_cell(cfg, shape, mesh, rules, **pick("optimizer"))
    elif cfg.family == "taper":
        plan = _taper_cell(cfg, shape, mesh, rules,
                           **pick("dense_ext_to"))
    else:
        raise ValueError(cfg.family)
    plan.mesh = mesh
    plan.rules = rules
    plan.constrain_activations = constrain_activations
    return plan


def all_cells():
    """Every (arch, shape) pair in the assignment (skips documented in
    configs.registry.shapes_for)."""
    out = []
    from repro.configs.registry import list_archs

    for arch in list_archs():
        for s in shapes_for(arch):
            out.append((arch, s.name))
    return out
