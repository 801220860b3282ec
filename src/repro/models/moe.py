"""Mixture-of-Experts layer: top-k routing with sort-based capacity dispatch.

Switch/GShard-style (taxonomy §B.2): tokens are routed to their top-k
experts, laid out into an ``(experts, capacity, d)`` buffer via a sort by
expert id (O(T k log) — no (T, E) one-hot materialisation, which matters at
384 experts x 1M tokens), processed by per-expert SwiGLU FFNs, and combined
with router weights.  Tokens beyond an expert's capacity are dropped (their
residual stream passes through unchanged).

Two expert-parallel execution paths:

* ``apply``          — single-program scatter/gather.  Correct everywhere,
  but under GSPMD the global (T*K)-indexed scatter/gather cannot be
  partitioned: its gradient materialises full (T*K, d) fp32 tensors and
  all-reduces them (§Perf-K1 measured ~970 GB/step wire on kimi-k2 train).
* ``apply_sharded``  — shard_map expert parallelism (§Perf-K1 fix): experts
  live on their model shard, activations are already replicated across
  ``model``, each shard routes/dispatches purely locally and the combine is
  ONE psum of the (T_local, d) partial output — the same wire cost as any
  tensor-parallel layer.

``apply_auto`` picks the sharded path whenever a launch-layer mesh context
with a ``model`` axis is active (CPU unit tests see the plain path).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import MoEConfig
from repro.distributed.sharding import _ACT_CTX, constrain
from repro.models.layers import dense_init, swiglu


def init(rng, d_model: int, cfg: MoEConfig, dtype) -> Tuple[Dict, Dict]:
    ks = jax.random.split(rng, 5)
    E, F = cfg.n_experts, cfg.d_expert_ff
    s_in = 1.0 / math.sqrt(d_model)
    s_out = 1.0 / math.sqrt(F)
    params = {
        "router": {"w": (jax.random.normal(ks[0], (d_model, E), jnp.float32) * s_in)},
        "gate": jax.random.normal(ks[1], (E, d_model, F), jnp.float32) * s_in,
        "up": jax.random.normal(ks[2], (E, d_model, F), jnp.float32) * s_in,
        "down": jax.random.normal(ks[3], (E, F, d_model), jnp.float32) * s_out,
    }
    logical = {
        "router": {"w": ("fsdp", None)},
        "gate": ("experts", "fsdp", None),
        "up": ("experts", "fsdp", None),
        "down": ("experts", None, "fsdp"),
    }
    params = jax.tree.map(lambda x: x.astype(dtype), params)
    if cfg.n_shared:
        ks2 = jax.random.split(ks[4], 3)
        params["shared"] = {
            "gate": (jax.random.normal(ks2[0], (cfg.n_shared, d_model, F), jnp.float32) * s_in).astype(dtype),
            "up": (jax.random.normal(ks2[1], (cfg.n_shared, d_model, F), jnp.float32) * s_in).astype(dtype),
            "down": (jax.random.normal(ks2[2], (cfg.n_shared, F, d_model), jnp.float32) * s_out).astype(dtype),
        }
        logical["shared"] = {
            "gate": (None, "fsdp", "model"),
            "up": (None, "fsdp", "model"),
            "down": (None, "model", "fsdp"),
        }
    return params, logical


def apply(params, x: jnp.ndarray, cfg: MoEConfig,
          capacity: Optional[int] = None) -> Tuple[jnp.ndarray, Dict]:
    """x: (T, d) token-major. Returns (out (T, d), aux metrics/losses)."""
    T, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    C = capacity or max(1, int(math.ceil(T * K / E * cfg.capacity_factor)))

    logits = (x @ params["router"]["w"].astype(x.dtype)).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topk_p, topk_e = jax.lax.top_k(probs, K)                   # (T, K)
    topk_p = topk_p / jnp.maximum(topk_p.sum(-1, keepdims=True), 1e-9)

    # ---- sort-based dispatch ----
    flat_e = topk_e.reshape(-1)                                # (T*K,)
    order = jnp.argsort(flat_e)                                # stable
    sorted_e = flat_e[order]
    # rank within expert: position in sorted array minus expert start
    starts = jnp.searchsorted(sorted_e, jnp.arange(E))         # (E,)
    rank = jnp.arange(T * K) - starts[sorted_e]
    keep = rank < C
    slot = jnp.where(keep, sorted_e * C + rank, E * C)         # E*C = drop bin
    token_of = order // K

    buf = jnp.zeros((E * C + 1, d), dtype=x.dtype)
    buf = buf.at[slot].add(x[token_of])                        # scatter tokens
    buf = constrain(buf[: E * C].reshape(E, C, d), "experts", None, None)

    # ---- expert FFNs (grouped einsum over the expert dim) ----
    h = jnp.einsum("ecd,edf->ecf", buf, params["gate"].astype(x.dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, params["up"].astype(x.dtype))
    y = jnp.einsum("ecf,efd->ecd", swiglu(h, u), params["down"].astype(x.dtype))
    y = constrain(y, "experts", None, None)

    # ---- combine ----
    y_flat = y.reshape(E * C, d)
    w_sorted = topk_p.reshape(-1)[order].astype(x.dtype)
    gathered = jnp.where(
        keep[:, None], y_flat[jnp.minimum(slot, E * C - 1)], 0.0
    ) * w_sorted[:, None]
    out = constrain(
        jnp.zeros((T, d), dtype=x.dtype).at[token_of].add(gathered),
        "batch", None)

    if "shared" in params:
        sp = params["shared"]
        for i in range(sp["gate"].shape[0]):
            h = x @ sp["gate"][i].astype(x.dtype)
            u = x @ sp["up"][i].astype(x.dtype)
            out = out + swiglu(h, u) @ sp["down"][i].astype(x.dtype)

    # ---- router losses (Switch aux load-balance + z-loss) ----
    me = probs.mean(axis=0)                                    # (E,)
    ce = jnp.zeros((E,)).at[flat_e].add(1.0) / (T * K)
    aux_loss = cfg.aux_coef * E * jnp.sum(me * ce)
    z_loss = cfg.router_z_coef * jnp.mean(
        jax.scipy.special.logsumexp(logits, axis=-1) ** 2
    )
    dropped = 1.0 - keep.mean()
    aux = {
        "moe_aux_loss": aux_loss,
        "moe_z_loss": z_loss,
        "moe_dropped_frac": dropped,
    }
    return out, aux


# ---------------------------------------------------------------------------
# shard_map expert parallelism (§Perf-K1)
# ---------------------------------------------------------------------------


def apply_sharded(params, x: jnp.ndarray, cfg: MoEConfig, mesh, rules,
                  capacity: Optional[int] = None) -> Tuple[jnp.ndarray, Dict]:
    """Expert-parallel MoE via shard_map.

    x: (T, d), sharded over the batch axes and replicated over ``model``.
    Expert weights (E, d, F) are sharded over ``model``.  Each model shard
    routes its (replicated) tokens against the global router, keeps only
    the assignments that hit its local experts, runs the local expert FFNs,
    and contributes a partial (T_local, d) output; psum over ``model``
    completes the combine.  No global scatter/gather ever crosses shards.
    """
    T, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    batch_axes = rules.lookup("batch")
    batch_axes = (batch_axes,) if isinstance(batch_axes, str) else tuple(batch_axes)
    model_axis = "model"
    n_model = mesh.shape[model_axis]
    E_loc = E // n_model
    n_batch = 1
    for a in batch_axes:
        n_batch *= mesh.shape[a]
    T_loc = T // n_batch
    C = capacity or max(1, int(math.ceil(T_loc * K / E * cfg.capacity_factor)))

    x_spec = P(batch_axes, None)
    router_spec = P(None, None)
    ew_spec = P(model_axis, None, None)
    ew_spec_out = P(model_axis, None, None)

    def local_moe(xb, router_w, gate, up, down):
        # xb: (T_loc, d) — identical on every model shard of this data row
        my_rank = jax.lax.axis_index(model_axis)
        logits = (xb @ router_w.astype(xb.dtype)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        topk_p, topk_e = jax.lax.top_k(probs, K)
        topk_p = topk_p / jnp.maximum(topk_p.sum(-1, keepdims=True), 1e-9)

        flat_e = topk_e.reshape(-1)
        flat_p = topk_p.reshape(-1)
        local_id = flat_e - my_rank * E_loc
        mine = (local_id >= 0) & (local_id < E_loc)

        order = jnp.argsort(jnp.where(mine, local_id, E_loc))
        sorted_lid = jnp.where(mine, local_id, E_loc)[order]
        starts = jnp.searchsorted(sorted_lid, jnp.arange(E_loc))
        rank = jnp.arange(T_loc * K) - starts[jnp.minimum(sorted_lid, E_loc - 1)]
        keep = (sorted_lid < E_loc) & (rank < C)
        slot = jnp.where(keep, sorted_lid * C + rank, E_loc * C)
        token_of = order // K

        buf = jnp.zeros((E_loc * C + 1, d), dtype=xb.dtype)
        buf = buf.at[slot].add(xb[token_of])
        buf = buf[: E_loc * C].reshape(E_loc, C, d)

        h = jnp.einsum("ecd,edf->ecf", buf, gate.astype(xb.dtype))
        u = jnp.einsum("ecd,edf->ecf", buf, up.astype(xb.dtype))
        y = jnp.einsum("ecf,efd->ecd", swiglu(h, u), down.astype(xb.dtype))

        y_flat = y.reshape(E_loc * C, d)
        w_sorted = flat_p[order].astype(xb.dtype)
        gathered = jnp.where(
            keep[:, None], y_flat[jnp.minimum(slot, E_loc * C - 1)], 0.0
        ) * w_sorted[:, None]
        partial = jnp.zeros((T_loc, d), dtype=xb.dtype).at[token_of].add(gathered)
        out = jax.lax.psum(partial, model_axis)

        # router losses (identical on all model shards; psum the kept count)
        me = probs.mean(axis=0)
        ce = jnp.zeros((E,)).at[flat_e].add(1.0) / (T_loc * K)
        aux_loss = jnp.asarray(cfg.aux_coef * E * jnp.sum(me * ce))
        z_loss = jnp.asarray(cfg.router_z_coef * jnp.mean(
            jax.scipy.special.logsumexp(logits, axis=-1) ** 2))
        kept = jax.lax.psum(keep.sum(), model_axis)
        dropped = 1.0 - kept.astype(jnp.float32) / (T_loc * K)
        return out, aux_loss[None], z_loss[None], dropped[None]

    shard_spec = P(batch_axes)
    out, aux_loss, z_loss, dropped = jax.shard_map(
        local_moe, mesh=mesh,
        in_specs=(x_spec, router_spec, ew_spec, ew_spec, ew_spec_out),
        out_specs=(x_spec, shard_spec, shard_spec, shard_spec),
        check_vma=False,
    )(x, params["router"]["w"], params["gate"], params["up"], params["down"])
    aux_loss, z_loss, dropped = (aux_loss.mean(), z_loss.mean(), dropped.mean())

    if "shared" in params:
        sp = params["shared"]
        for i in range(sp["gate"].shape[0]):
            h = x @ sp["gate"][i].astype(x.dtype)
            u = x @ sp["up"][i].astype(x.dtype)
            out = out + swiglu(h, u) @ sp["down"][i].astype(x.dtype)

    aux = {
        "moe_aux_loss": aux_loss,
        "moe_z_loss": z_loss,
        "moe_dropped_frac": dropped,
    }
    return out, aux


def apply_auto(params, x: jnp.ndarray, cfg: MoEConfig) -> Tuple[jnp.ndarray, Dict]:
    """Sharded path when a mesh context with a model axis is active."""
    ctx = _ACT_CTX.get()
    if ctx is not None:
        mesh, rules = ctx
        if "model" in mesh.axis_names and cfg.n_experts % mesh.shape["model"] == 0:
            return apply_sharded(params, x, cfg, mesh, rules)
    return apply(params, x, cfg)
