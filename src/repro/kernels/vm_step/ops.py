"""Wrapper: reuses segment_spmm's edge packing; adds label/inv-cnt channels."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.kernels.segment_spmm.ops import PackedEdges, pack_edges
from repro.kernels.vm_step.kernel import vm_step_packed


def pack_vm_inputs(edge_src, edge_dst, labels, cnt, n: int,
                   block_n: int = 128, block_e: int = 256):
    """Pack edges (sorted by dst) and per-edge label / 1/cnt channels."""
    packed = pack_edges(edge_src, edge_dst, n, block_n, block_e)
    order = packed.order  # pack_edges already sorted by dst; reuse its order
    dst_lab_sorted = np.asarray(labels)[np.asarray(edge_dst)[order]]
    src_sorted = np.asarray(edge_src)[order]
    inv = 1.0 / np.maximum(
        np.asarray(cnt)[src_sorted, dst_lab_sorted], 1.0)
    E_pad = packed.src.shape[0]
    dst_label = np.zeros(E_pad, np.int32)
    inv_cnt = np.zeros(E_pad, np.float32)
    dst_label[packed.pad_mask] = dst_lab_sorted
    inv_cnt[packed.pad_mask] = inv
    return packed, jnp.asarray(dst_label), jnp.asarray(inv_cnt)


def vm_step(
    alpha: jnp.ndarray,
    T: jnp.ndarray,
    packed: PackedEdges,
    dst_label: jnp.ndarray,
    inv_cnt: jnp.ndarray,
    n: int,
) -> jnp.ndarray:
    """One DP step ``(n, N) -> (n, N)`` over a :func:`pack_vm_inputs`
    packing: gathers the source rows in XLA and runs the kernel on them."""
    a_src_t = jnp.take(alpha.T, jnp.asarray(packed.src), axis=1)
    out_t = vm_step_packed(
        a_src_t, T, jnp.asarray(packed.dst_local), dst_label, inv_cnt,
        jnp.asarray(packed.meta.T), packed.n_blocks_out, packed.block_n,
        packed.block_e)
    return out_t[:, :n].T
