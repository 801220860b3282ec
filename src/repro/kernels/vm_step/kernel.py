"""TAPER Visitor-Matrix DP edge-propagation TPU kernel.

The paper's Alg. 1 hot loop, reformulated (DESIGN.md §2) as a label-masked
SpMM.  Same packing contract as segment_spmm (edges sorted by destination,
one destination block per edge block, scalar-prefetched output index), with
the per-edge trie transition fused in.  The kernel works feature-major: the
trie-state axis N leads and edges / vertices run along the lanes.

    in XLA, before the kernel:  At = alphaT[:, src]               (N, E_pad)
    per edge block:             Mt = sum_l [label(dst) == l] * (T[l]^T @ At)
                                Mt *= 1 / cnt
                                outT += Mt @ onehot(dst_local)     MXU scatter

Nothing is gathered inside the kernel: the TPU compiler refuses vector
integer indexing of a VMEM ref, so the source columns arrive pre-gathered
as ``(N, block_e)`` blocks, and the destination-label transition is a
static loop over the L labels, each step one 2-D matmul masked by
``dst_label == l``.  The trie transition tensor T (L x N x N, ~ 12x24x24
floats) lives wholly in VMEM — the intensional workload summary is small by
construction (paper §4).

Layouts that tile on the chip without padding copies:

* ``(N, E_pad)`` source columns: N (~24) rows pad to a multiple of 8, where
  an ``(E_pad, N)`` operand would pad N to 128 lanes (5x the bytes);
* per-edge channels (``dst_local``, ``dst_label``, ``inv_cnt``) as
  ``(1, E_pad)`` rows in ``(1, block_e)`` blocks.  1-D blocks are laid out
  ``T(1024)`` by XLA but ``T(256)`` by Mosaic and are refused, and
  ``(E_pad, 1)`` columns cost a relayout copy 128x their size;
* the scalar-prefetched block table ``meta`` as ``(2, EB)`` (row 0: output
  block, row 1: first-edge-block flag).  SMEM pads the minor dimension of a
  2-D array to 128 words, so ``(EB, 2)`` would cost 64x its size; ``(2,
  EB)`` costs ``8 * EB`` bytes, which the 1 MiB of SMEM bounds at
  :data:`MAX_EDGE_BLOCKS`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: edge blocks one call can take on a TPU v5e: the ``(2, EB)`` int32 block
#: table plus Mosaic's own scalars (~1 KiB of spill slots) must fit the
#: 1 MiB of SMEM; this leaves them 8 KiB.  A 1M-vertex, degree-6 graph
#: needs ~23K blocks of 256 edges; at 10M vertices the grid would have to
#: be split across calls.
MAX_EDGE_BLOCKS = ((1 << 20) - (8 << 10)) // 8


def default_interpret() -> bool:
    """Interpret-mode policy for the TAPER kernels: compiled by Mosaic on a
    TPU backend, run by the Pallas interpreter everywhere else."""
    return jax.default_backend() != "tpu"


def _vm_kernel(meta_ref, a_ref, dstloc_ref, dstlab_ref, invcnt_ref, Tt_ref,
               o_ref, *, block_n: int, n_labels: int):
    e_i = pl.program_id(0)

    @pl.when(meta_ref[1, e_i] == 1)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    At = a_ref[...]                           # (N, block_e)
    dst_lab = dstlab_ref[...]                 # (1, block_e)
    Mt = jnp.zeros(At.shape, jnp.float32)
    for l in range(n_labels):
        step = jnp.dot(Tt_ref[l], At, preferred_element_type=jnp.float32,
                       precision=jax.lax.Precision.HIGHEST)
        Mt = Mt + jnp.where(dst_lab == l, step, 0.0)
    Mt = Mt * invcnt_ref[...]                 # 0 on padded edges
    dst_loc = dstloc_ref[...]                 # (1, block_e)
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_n, dst_loc.shape[1]), 0)
    onehot = (rows == dst_loc).astype(jnp.float32)   # (block_n, block_e)
    contrib = jax.lax.dot_general(
        Mt, onehot, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)  # (N, block_n)
    o_ref[...] += contrib.astype(o_ref.dtype)


def vm_step_packed(
    a_src_t: jnp.ndarray,      # (N, E_pad) alphaT[:, src], gathered by caller
    T: jnp.ndarray,            # (L, N, N)
    dst_local: jnp.ndarray,    # (E_pad,)
    dst_label: jnp.ndarray,    # (E_pad,)
    inv_cnt: jnp.ndarray,      # (E_pad,) 0 on padding
    meta: jnp.ndarray,         # (2, EB) [dst_block_id; is_first]
    n_blocks_out: int,
    block_n: int,
    block_e: int,
) -> jnp.ndarray:
    """One DP step over packed edge blocks, feature-major: returns the
    ``(N, n_blocks_out * block_n)`` transposed next state.  Interpret mode
    follows :func:`default_interpret`."""
    N, E_pad = a_src_t.shape
    L = T.shape[0]
    EB = E_pad // block_e
    if meta.shape != (2, EB):
        raise ValueError(f"meta must be (2, {EB}), got {meta.shape}")
    row = lambda x: jnp.reshape(x, (1, E_pad))
    kernel = functools.partial(_vm_kernel, block_n=block_n, n_labels=L)
    edge_spec = pl.BlockSpec((1, block_e), lambda e, meta: (0, e))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(EB,),
        in_specs=[
            pl.BlockSpec((N, block_e), lambda e, meta: (0, e)),
            edge_spec,
            edge_spec,
            edge_spec,
            pl.BlockSpec((L, N, N), lambda e, meta: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((N, block_n), lambda e, meta: (0, meta[0, e])),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, n_blocks_out * block_n),
                                       a_src_t.dtype),
        interpret=default_interpret(),
    )(meta, a_src_t, row(dst_local), row(dst_label), row(inv_cnt),
      jnp.swapaxes(T, 1, 2))
