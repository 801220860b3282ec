"""Wrapper: CSR packing (host-side, cached) + pallas_call + XLA fallback.

``pack_edges`` sorts edges by destination and pads each destination block's
edge list to a multiple of ``block_e``, so every edge block belongs to
exactly one output block (the kernel's scalar-prefetch contract).  Padding
edges carry weight 0 and scatter to row 0 of their block (a no-op).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.segment_spmm.kernel import segment_spmm_packed
from repro.kernels.segment_spmm.ref import segment_spmm_reference


@dataclass
class PackedEdges:
    src: np.ndarray          # (E_pad,)
    dst_local: np.ndarray    # (E_pad,)
    meta: np.ndarray         # (EB, 2) [dst_block_id, is_first]
    pad_mask: np.ndarray     # (E_pad,) True on real edges
    order: np.ndarray        # (E,) stable argsort of edge_dst: raw -> packed order
    n_blocks_out: int
    block_n: int
    block_e: int


def pack_edges(edge_src: np.ndarray, edge_dst: np.ndarray, n: int,
               block_n: int = 128, block_e: int = 256) -> PackedEdges:
    edge_src = np.asarray(edge_src)
    edge_dst = np.asarray(edge_dst)
    order = np.argsort(edge_dst, kind="stable")
    src_s, dst_s = edge_src[order], edge_dst[order]
    n_blocks_out = (n + block_n - 1) // block_n
    blk = (dst_s // block_n).astype(np.int64)

    # each destination block's edges fill its own run of edge blocks (at
    # least one), padded to a multiple of block_e
    cnt = np.bincount(blk, minlength=n_blocks_out)
    n_eb = np.maximum(1, -(-cnt // block_e))
    slot0 = np.concatenate([[0], np.cumsum(n_eb)]) * block_e
    edge0 = np.concatenate([[0], np.cumsum(cnt)])
    slot = slot0[blk] + np.arange(blk.size) - edge0[blk]
    e_pad = int(slot0[-1])
    src = np.zeros(e_pad, np.int32)
    dst_local = np.zeros(e_pad, np.int32)
    pad_mask = np.zeros(e_pad, bool)
    src[slot] = src_s
    dst_local[slot] = dst_s - blk * block_n
    pad_mask[slot] = True
    meta = np.zeros((int(n_eb.sum()), 2), np.int32)
    meta[:, 0] = np.repeat(np.arange(n_blocks_out), n_eb)
    meta[slot0[:-1] // block_e, 1] = 1
    return PackedEdges(
        src=src,
        dst_local=dst_local,
        meta=meta,
        pad_mask=pad_mask,
        order=order,
        n_blocks_out=n_blocks_out,
        block_n=block_n,
        block_e=block_e,
    )


def segment_spmm(
    x: jnp.ndarray,
    packed: PackedEdges,
    edge_w: jnp.ndarray,       # (E_pad,) weights aligned with packed order
    n_out: int,
    interpret: bool = True,
    use_pallas: bool = True,
    block_f: int = 0,
) -> jnp.ndarray:
    """Compute out[dst] += w_e * x[src] over packed edges; returns (n_out, F)."""
    if not use_pallas:
        # reconstruct global destinations from the packing
        dst_block = np.repeat(packed.meta[:, 0], packed.block_e)
        dst_global = jnp.asarray(dst_block * packed.block_n) + jnp.asarray(
            packed.dst_local)
        return segment_spmm_reference(
            x, jnp.asarray(packed.src), dst_global, edge_w, n_out)
    out = segment_spmm_packed(
        x,
        jnp.asarray(packed.src),
        jnp.asarray(packed.dst_local),
        edge_w,
        jnp.asarray(packed.meta),
        packed.n_blocks_out,
        packed.block_n,
        packed.block_e,
        block_f=block_f,
        interpret=interpret,
    )
    return out[:n_out]


def pack_weights(packed: PackedEdges, edge_w) -> jnp.ndarray:
    """Reorder raw per-edge weights into packed order (0 on padding).

    ``edge_w`` must align with the raw edge list the packing was built from;
    the dst-sort order recorded at pack time is applied directly.
    """
    w_sorted = np.asarray(edge_w)[packed.order]
    out = np.zeros(packed.src.shape[0], w_sorted.dtype)
    out[packed.pad_mask] = w_sorted
    return jnp.asarray(out)
