"""Vectorised Visitor-Matrix extroversion field (paper §2.3, §3.2, §5.4).

The paper's Alg. 1 builds Visitor-Matrix rows corecursively per vertex.  We
reformulate it as a depth-stratified sparse recurrence over the edge list —
the TPU-native adaptation (DESIGN.md §2):

  state    alpha[v, n]  = total probability of workload-legal *intra-partition*
                          paths ending at v whose label string is trie node n
  base     alpha[v, n1] = p(n1) / |{u : l(u) = label(n1)}|        (depth-1 n1)
  step     alpha[w, n'] += alpha[u, parent(n')] * cond_p(n')
                           / cnt[u, l(w)]          over local edges (u, w)
  masses   mass[u→w]    = sum_n alpha[u, parent(c)] * cond_p(c) / cnt[u, l(w)]
                          for c = child(n, l(w))   over ALL edges
  outputs  Pr(v)        = sum_{n non-leaf} alpha[v, n]
           extroversion = (sum of mass over cut edges out of v) / Pr(v)
           introversion = 1 - extroversion  (termination mass is intra, §4.2)

Everything is a sum over edge blocks — the same kernel regime as GNN
message passing (the fused program sums each destination's contiguous
in-edge segment of a destination-sorted edge order);
`repro.kernels.vm_step` provides the Pallas TPU kernel for the inner step,
and this module is its jnp oracle.

One jit cache entry exists per (trie topology, graph/partition shapes); trie
*probabilities* are runtime arguments so workload-frequency drift never
recompiles.

Multi-device (``backend="pallas_sharded"``): the packed edge blocks are
dealt across the mesh's ``model`` axis (``LabelledGraph.vm_packing_sharded``,
along a pluggable topology-aware *shard map* — see
``repro.graphs.sharded_packing``) and the depth loop runs under
``shard_map`` as a **halo-exchange recurrence** with two exchange backends:

* ``halo_exchange="sliced"`` (default) — two-tier per-shard-pair slice
  exchange: hub rows read by many shards travel once in a small psum'd
  *hot union*, and the cold tail moves as a ragged all-to-all decomposed
  into ``S - 1`` ring ``ppermute`` rounds, each padded only to that
  round's largest pair (the packing's precomputed ``send_local`` tables
  and ``round_cap``).  Per-depth traffic is ``(hot_pad + sum(round_cap))
  * N`` floats per shard — it scales with what each shard *reads*, not
  with the global union, so a topology-aware shard map (e.g.
  ``"partition"``) compresses it directly;
* ``halo_exchange="psum"`` — the PR-3 union exchange, kept as a fallback
  for latency-bound meshes where ``S - 1`` collective rounds lose to one
  ``psum`` (and for layouts whose pairwise halos approach the union
  anyway): every shard scatters its owned slice of the union frontier
  into an ``(H_pad, N)`` buffer and one ``psum`` completes it (each
  frontier row has exactly one owner).

Either way each shard then advances its local destination blocks with the
``vm_step`` kernel, gathering sources from ``concat([beta_local,
exchanged])`` via the packing's mode-matched source map (``src_map`` /
``src_map_sliced``), and per-slot edge masses accumulate shard-locally
(over *all* edges, cut and local).

Because destination blocks never cross shards, the kernel's output rows
are wholly shard-local and ``alpha`` assembles by concatenation — in
*position* space; the shard map's inverse permutation restores vertex
order (a no-op gather under the identity stripe map).  The loop, that
reorder, the scatter of slot masses into raw edge order (through the
packing's ``slot_raw``, uploaded with the shard arrays) and the
aggregates are one jitted program: no host round trip inside a call.
After graph mutations, stale device buffers re-upload per *dirty shard*
(the packing's ``shard_epoch`` counters), not wholesale.  Each sharded
evaluation records
its measured exchange footprint in ``pre["_halo_stats"]`` (bytes per depth
step, halo ratio vs the full field, shard-map source, exchange backend)
for serving metrics and benchmarks.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tpstry import TrieArrays
from repro.graphs.graph import LabelledGraph
from repro.obs.trace import NOOP_SPAN, Span
from repro.utils import get_logger

log = get_logger("core.visitor")

_EPS = 1e-30


@dataclass
class ExtroversionResult:
    """Per-vertex/per-edge extroversion field for one partitioning."""

    alpha: np.ndarray         # (n, N) path-state probabilities
    pr: np.ndarray            # (n,)  total traversal probability through v
    edge_mass: np.ndarray     # (m,)  traversal probability mass per directed edge
    extro_mass: np.ndarray    # (n,)  external mass out of v
    extroversion: np.ndarray  # (n,)  extro_mass / pr  (0 where pr == 0)
    ext_to: Optional[np.ndarray]  # (n, k) external mass per destination part
                                  # (None under the two-phase §Perf-T2 path:
                                  # swap computes candidate rows lazily)
    total_extroversion: float  # sum of extro_mass — TAPER's objective

    @property
    def introversion(self) -> np.ndarray:
        return np.where(self.pr > 0, 1.0 - self.extroversion, 1.0)


# ---------------------------------------------------------------------------
# jit core (cached per trie topology + shapes)
# ---------------------------------------------------------------------------

_FIELD_CACHE: Dict[Tuple, object] = {}


def _prior_columns(depth, labels_n, N, vlabels, lab_vcount, p, n):
    """Depth-1 prior columns ``alpha[v, n1] = p(n1) / |{u : l(u)=label(n1)}|``.

    Shared by the jnp and Pallas backends so the base case is arithmetically
    identical (float32 division on-device) in both."""
    cols = []
    for i in range(N):
        if depth[i] == 1:
            li = int(labels_n[i])
            prior = p[i] / jnp.maximum(lab_vcount[li].astype(jnp.float32), 1.0)
            cols.append(jnp.where(vlabels == li, prior, 0.0))
        else:
            cols.append(jnp.zeros((n,), dtype=jnp.float32))
    return jnp.stack(cols, axis=1) if N else jnp.zeros((n, 0), jnp.float32)


def _field_aggregates(counted_nodes, k, dense_ext_to,
                      alpha, mass, src, dst, part, local, n):
    """Pr / extroversion / (optional) ext_to tail, shared by both backends."""
    pr = jnp.zeros((n,), dtype=jnp.float32)
    for i in counted_nodes:
        pr = pr + alpha[:, i]
    is_ext = 1.0 - local
    extro_mass = jax.ops.segment_sum(mass * is_ext, src, num_segments=n)
    extroversion = jnp.where(pr > _EPS, extro_mass / jnp.maximum(pr, _EPS), 0.0)
    if dense_ext_to:
        seg = src.astype(jnp.int32) * k + part[dst]
        ext_to = jax.ops.segment_sum(mass * is_ext, seg, num_segments=n * k)
        return alpha, pr, mass, extro_mass, extroversion, ext_to.reshape(n, k)
    return alpha, pr, mass, extro_mass, extroversion


def _segment_sums(x, seg, ptr, levels: int):
    """Sums of the rows of ``x`` over contiguous segments, with no scatter.

    Row ``i`` of ``x`` (``(m, c)``) belongs to segment ``seg[i]``
    (non-decreasing), and segment ``v`` is rows ``ptr[v]:ptr[v + 1]``
    (``ptr`` is ``(n + 1,)``).  A Hillis–Steele inclusive scan adds row
    ``i - 2**j`` into row ``i`` at level ``j`` only when both lie in one
    segment, so after ``levels`` levels (the bit length of the longest
    segment less one) a segment's last row holds its total and no sum
    takes a row of another segment.  Returns ``(n, c)``, zero for an
    empty segment.
    """
    m = x.shape[0]
    if m == 0:
        return jnp.zeros((ptr.shape[0] - 1, x.shape[1]), x.dtype)
    for j in range(levels):
        s = 1 << j
        if s >= m:
            break
        # row i - s, shifted in by a pad (one fused pass a level)
        prev = jnp.pad(x, ((s, 0), (0, 0)))[:m]
        same = jnp.pad(seg, (s, 0), constant_values=-1)[:m] == seg
        x = x + jnp.where(same[:, None], prev, 0.0)
    end = jnp.maximum(ptr[1:] - 1, 0)
    return jnp.where((ptr[1:] > ptr[:-1])[:, None], x[end], 0.0)


def _segment_aggregates(counted_nodes, k, dense_ext_to, alpha, mass,
                        src, dst, part, local, row_ptr, levels, n):
    """:func:`_field_aggregates` for edges in CSR order: each vertex's
    out-edges are one segment, so the external mass and ``ext_to`` are
    :func:`_segment_sums` over ``row_ptr`` in one pass."""
    pr = jnp.zeros((n,), dtype=jnp.float32)
    for i in counted_nodes:
        pr = pr + alpha[:, i]
    ext = mass * (1.0 - local)
    cols = [ext[:, None]]
    if dense_ext_to:
        to = part[dst][:, None] == jnp.arange(k, dtype=part.dtype)[None, :]
        cols.append(jnp.where(to, ext[:, None], 0.0))
    sums = _segment_sums(jnp.concatenate(cols, axis=1), src, row_ptr, levels)
    extro_mass = sums[:, 0]
    extroversion = jnp.where(pr > _EPS, extro_mass / jnp.maximum(pr, _EPS), 0.0)
    if dense_ext_to:
        return alpha, pr, mass, extro_mass, extroversion, sums[:, 1:]
    return alpha, pr, mass, extro_mass, extroversion


def _build_field_fn(topology: Tuple, trie: TrieArrays, k: int, depth_cap: int,
                    fused: bool = True, dense_ext_to: bool = True):
    """Build the jitted field function for a fixed trie *topology*.

    Topology (parent/label/leaf structure) is baked in as Python-level loop
    structure; probabilities arrive as runtime arrays.

    Each phase of the program runs under a ``jax.named_scope``, so the
    device trace's ops carry it in their ``op_name``: ``field.priors``
    (per-edge inputs and the depth-1 priors), ``field.depth<d>`` (one DP
    depth step) and ``field.aggregates`` (Pr, extroversion, ``ext_to``).

    Two implementations (numerically identical; tested against each other):

    * naive  — one gather + segment_sum pass over the edge list per trie
      node (the direct transcription of the recurrence);
    * fused  — all trie nodes of one depth advance in a single batched
      gather / elementwise pass (§Perf iteration T1: the naive variant
      launches ~N_trie scatter passes whose intermediates cannot fuse, and
      its HBM term is ~5x the fused one).  It runs over the edges in
      destination order (``LabelledGraph.in_edge_order``), so each
      destination's sum is :func:`_segment_sums` over its contiguous
      in-edge segment rather than a scatter-add by unsorted ``dst`` (on
      a TPU a scatter costs per update row); the aggregates likewise sum
      contiguous out-edge segments.  ``levels`` (static) is the bit length
      of the longest segment less one.
    """
    parent = trie.parent.copy()
    labels_n = trie.label.copy()
    depth = trie.depth.copy()
    is_leaf = trie.is_leaf.copy()
    N = trie.n_nodes
    max_depth = min(trie.max_depth, depth_cap)

    step_nodes = [
        i for i in range(N) if 2 <= depth[i] <= max_depth
    ]  # in depth order already (compile() sorts by depth)
    # states that still have outgoing transitions (non-leaf, depth in [1, t))
    counted_nodes = [
        i for i in range(N)
        if 1 <= depth[i] < max_depth and not is_leaf[i]
    ]

    def _inputs_and_priors(src, dst, vlabels, cnt, lab_vcount, part, p, n):
        with jax.named_scope("field.priors"):
            inv_cnt = 1.0 / jnp.maximum(cnt.astype(jnp.float32), 1.0)  # (n, L)
            local = (part[src] == part[dst]).astype(jnp.float32)       # (m,)
            dst_lab = vlabels[dst]                                     # (m,)
            alpha = _prior_columns(depth, labels_n, N, vlabels, lab_vcount,
                                   p, n)
        return inv_cnt, local, dst_lab, alpha

    def _aggregates(alpha, mass, src, dst, part, local, n):
        with jax.named_scope("field.aggregates"):
            return _field_aggregates(counted_nodes, k, dense_ext_to,
                                     alpha, mass, src, dst, part, local, n)

    @partial(jax.jit, static_argnames=("n", "m"))
    def field_fn_naive(
        src, dst, vlabels, cnt, lab_vcount, part, p, cond_p, *, n: int, m: int
    ):
        inv_cnt, local, dst_lab, alpha = _inputs_and_priors(
            src, dst, vlabels, cnt, lab_vcount, part, p, n)

        # --- DP steps + edge masses, one pass per depth>=2 node ---
        mass = jnp.zeros((m,), dtype=jnp.float32)
        for c in step_nodes:
            par, lc = int(parent[c]), int(labels_n[c])
            with jax.named_scope(f"field.depth{int(depth[c])}"):
                contrib = (
                    alpha[src, par]
                    * cond_p[c]
                    * inv_cnt[src, lc]
                    * (dst_lab == lc).astype(jnp.float32)
                )
                mass = mass + contrib
                # only local (intra-partition) extensions continue the path
                alpha = alpha.at[:, c].add(
                    jax.ops.segment_sum(contrib * local, dst, num_segments=n)
                )
        return _aggregates(alpha, mass, src, dst, part, local, n)

    @partial(jax.jit, static_argnames=("n", "m", "levels"))
    def field_fn_fused(
        src, dst, vlabels, cnt, lab_vcount, part, p, cond_p,
        src_in, dst_in, rank_in, in_ptr, row_ptr, *, n: int, m: int,
        levels: int,
    ):
        # the edges twice: in CSR order (src, dst) and in destination order
        # (src_in, dst_in; rank_in maps a CSR edge to its position there),
        # where every vertex's in-edges are one segment of in_ptr
        with jax.named_scope("field.priors"):
            inv_cnt = 1.0 / jnp.maximum(cnt.astype(jnp.float32), 1.0)  # (n, L)
            local = (part[src] == part[dst]).astype(jnp.float32)       # (m,)
            local_in = (part[src_in] == part[dst_in]).astype(jnp.float32)
            lab_in = vlabels[dst_in]
            # 1 / cnt[u, l(w)] of each edge (u, w)
            ic_in = inv_cnt.reshape(-1)[src_in * cnt.shape[1] + lab_in]
            alpha = _prior_columns(depth, labels_n, N, vlabels, lab_vcount,
                                   p, n)
        cols = [alpha[:, i] for i in range(N)]

        mass_in = jnp.zeros((m,), dtype=jnp.float32)
        for d in range(2, max_depth + 1):
            nodes_d = [c for c in step_nodes if depth[c] == d]
            if not nodes_d:
                continue
            labs = jnp.asarray(np.asarray([labels_n[c] for c in nodes_d]))
            with jax.named_scope(f"field.depth{d}"):
                # one batched gather of the parent columns at each edge's
                # source, in destination order: (m, n_d)
                a_par = jnp.stack([cols[int(parent[c])] for c in nodes_d],
                                  axis=1)[src_in]
                coef = cond_p[jnp.asarray(np.asarray(nodes_d))][None, :]
                w = jnp.where(lab_in[:, None] == labs[None, :],
                              ic_in[:, None], 0.0)
                contrib = a_par * coef * w
                mass_in = mass_in + contrib.sum(axis=1)
                # each destination sums its own in-edge segment: (n, n_d)
                upd = _segment_sums(contrib * local_in[:, None], dst_in,
                                    in_ptr, levels)
            for j, c in enumerate(nodes_d):
                cols[c] = upd[:, j]
        with jax.named_scope("field.aggregates"):
            return _segment_aggregates(
                counted_nodes, k, dense_ext_to, jnp.stack(cols, axis=1),
                mass_in[rank_in], src, dst, part, local, row_ptr, levels, n)

    return field_fn_fused if fused else field_fn_naive


def _device_inputs(g: LabelledGraph, pre: Dict, cnt, lab_vcount) -> Dict:
    """Device-resident copies of the partition-independent field inputs.

    Cached inside the caller's ``_precomputed`` dict (Taper keeps one per
    graph), so repeated ``invoke`` iterations re-use the same device buffers
    instead of re-uploading the edge list every call.  Only the partition
    vector crosses host->device per iteration.  The graph's mutation
    ``version`` is recorded alongside the buffers: after
    ``LabelledGraph.apply_mutations`` the stale device-resident edge arrays
    are detected and re-uploaded rather than silently reused.
    """
    dev = pre.get("_dev")
    if dev is not None and pre.get("_dev_version") != g.version:
        dev = None
    if dev is None:
        dev = {
            "src": jnp.asarray(g.src),
            "dst": jnp.asarray(g.dst),
            "labels": jnp.asarray(g.labels),
            "cnt": jnp.asarray(cnt),
            "lab_vcount": jnp.asarray(lab_vcount),
        }
        pre["_dev"] = dev
        pre["_dev_version"] = g.version
    return dev


def _dst_order_inputs(g: LabelledGraph, dev: Dict) -> Dict:
    """Device copies of the graph's destination order, for the fused jnp
    program: the edges' sources and destinations in that order, each CSR
    edge's position in it (``rank``), the in-degree prefix ``in_ptr``, the
    CSR ``row_ptr`` and the static scan ``levels`` (bit length of the
    largest in- or out-degree less one).  Kept in the :func:`_device_inputs`
    dict, so they re-upload with ``src``/``dst`` after a mutation.
    """
    order = dev.get("dst_order")
    if order is None:
        perm, in_ptr = g.in_edge_order
        rank = np.empty(g.m, dtype=np.int32)
        rank[perm] = np.arange(g.m, dtype=np.int32)
        widest = max(int(np.diff(in_ptr).max(initial=0)),
                     int(np.diff(g.row_ptr).max(initial=0)))
        order = {
            "src": jnp.asarray(g.src[perm]),
            "dst": jnp.asarray(g.dst[perm]),
            "rank": jnp.asarray(rank),
            "in_ptr": jnp.asarray(in_ptr.astype(np.int32)),
            "row_ptr": jnp.asarray(g.row_ptr.astype(np.int32)),
            "levels": max(widest - 1, 0).bit_length(),
        }
        dev["dst_order"] = order
    return order


_TRANSITION_CACHE: Dict[Tuple, np.ndarray] = {}


def _capped_transition(trie: TrieArrays, depth_cap: int) -> np.ndarray:
    """(L, N, N) trie transition tensor with children beyond ``depth_cap``
    zeroed (§5.2.2 time heuristic).  Cached per (topology, probabilities);
    bounded so drifting workload frequencies (a fresh ``cond_p`` per
    invocation) cannot grow the cache without limit."""
    from repro.kernels.vm_step.ref import build_transition

    key = (trie.topology_signature(), int(depth_cap), trie.cond_p.tobytes())
    T = _TRANSITION_CACHE.get(key)
    if T is None:
        T = build_transition(trie.parent, trie.label, trie.cond_p,
                             trie.n_labels)
        if depth_cap < trie.max_depth:
            T[:, :, trie.depth > depth_cap] = 0.0
        while len(_TRANSITION_CACHE) >= 8:
            _TRANSITION_CACHE.pop(next(iter(_TRANSITION_CACHE)))
        _TRANSITION_CACHE[key] = T
    return T


@partial(jax.jit, static_argnames=("n", "n_blocks_out", "block_n", "block_e"))
def _pallas_depth_step(beta_t, T, Tsum, src, dst_lab, inv_cnt_edge,
                       packed_src, dst_local, dst_label, inv_local, meta_t,
                       *, n: int, n_blocks_out: int, block_n: int,
                       block_e: int):
    """One depth of the ``pallas`` field, feature-major (``beta_t`` is
    ``(N, n)``): the step's per-edge mass over ALL edges, and the next delta
    state over local edges through the ``vm_step`` kernel."""
    from repro.kernels.vm_step.kernel import vm_step_packed

    mass = (jnp.take(beta_t, src, axis=1)
            * jnp.take(Tsum.T, dst_lab, axis=1)).sum(axis=0) * inv_cnt_edge
    out_t = vm_step_packed(
        jnp.take(beta_t, packed_src, axis=1), T, dst_local, dst_label,
        inv_local, meta_t, n_blocks_out, block_n, block_e)
    return out_t[:, :n], mass


def _pallas_field(
    g: LabelledGraph,
    trie: TrieArrays,
    part: np.ndarray,
    k: int,
    depth_cap: int,
    pre: Dict,
    dense_ext_to: bool,
):
    """Pallas-backed extroversion field: the depth-advancing DP step runs as
    the ``vm_step`` TPU kernel over the graph's cached edge packing.

    The depth recurrence is expressed as a chain of *delta* states: ``beta_d``
    holds only the depth-``d`` trie columns, so applying the full transition
    tensor once per depth advances every state without double counting:

        beta_1 = priors;  beta_d = vm_step(beta_{d-1}, T | local edges)
        alpha  = sum_d beta_d
        mass  += rowsum over children of the beta_{d-1} messages (ALL edges)

    The states are kept feature-major (``(N, n)``), the kernel's layout.
    The packing (src/dst/label/1-cnt channels) is partition-independent and
    cached on the graph; per iteration only the partition vector and the
    derived local-edge mask move to the device.
    """
    n, m = g.n, g.m
    N = trie.n_nodes
    cnt = pre.get("cnt")
    if cnt is None:
        cnt = g.neighbor_label_counts()
    lab_vcount = pre.get("lab_vcount")
    if lab_vcount is None:
        lab_vcount = g.label_counts()
    dev = _device_inputs(g, pre, cnt, lab_vcount)
    src, dst, vlabels = dev["src"], dev["dst"], dev["labels"]

    packed, dst_label, inv_cnt_packed, dst_global = g.vm_packing(cnt=cnt)
    pdev = pre.get("_vm_dev")
    if pdev is not None and pre.get("_vm_dev_version") != g.version:
        pdev = None  # stale device packing from a pre-mutation graph
    if pdev is None:
        inv_cnt_edge = 1.0 / np.maximum(
            np.asarray(cnt)[g.src, g.labels[g.dst]], 1.0)
        pdev = {
            "packed_src": jnp.asarray(packed.src),
            "dst_local": jnp.asarray(packed.dst_local),
            "meta_t": jnp.asarray(packed.meta.T),
            "dst_global": jnp.asarray(dst_global),
            "inv_cnt_edge": jnp.asarray(inv_cnt_edge.astype(np.float32)),
        }
        pre["_vm_dev"] = pdev
        pre["_vm_dev_version"] = g.version

    # device-resident transition tensor, re-uploaded only when the trie
    # probabilities (or depth cap) change — not per iteration
    T_key = (trie.topology_signature(), int(depth_cap), trie.cond_p.tobytes())
    t_hit = pre.get("_T_dev")
    if t_hit is None or t_hit[0] != T_key:
        T = jnp.asarray(_capped_transition(trie, depth_cap))
        Tsum = T.sum(axis=2)                   # (L, N) mass per (label, parent)
        pre["_T_dev"] = (T_key, T, Tsum)
    else:
        _, T, Tsum = t_hit
    part_dev = jnp.asarray(part.astype(np.int32))
    local = (part_dev[src] == part_dev[dst]).astype(jnp.float32)   # (m,)
    local_packed = (part_dev[pdev["packed_src"]]
                    == part_dev[pdev["dst_global"]]).astype(jnp.float32)
    inv_local = inv_cnt_packed * local_packed  # 0 on padding (inv_cnt is 0)
    dst_lab = vlabels[dst]

    # depth-1 priors — same device arithmetic as the jnp backend
    alpha_t = _prior_columns(trie.depth, trie.label, N, vlabels,
                             dev["lab_vcount"], jnp.asarray(trie.p), n).T
    beta_t = alpha_t
    mass = jnp.zeros((m,), dtype=jnp.float32)
    max_depth = min(trie.max_depth, depth_cap)
    for _ in range(2, max_depth + 1):
        beta_t, step_mass = _pallas_depth_step(
            beta_t, T, Tsum, src, dst_lab, pdev["inv_cnt_edge"],
            pdev["packed_src"], pdev["dst_local"], dst_label, inv_local,
            pdev["meta_t"], n=n, n_blocks_out=packed.n_blocks_out,
            block_n=packed.block_n, block_e=packed.block_e)
        mass = mass + step_mass
        alpha_t = alpha_t + beta_t
    alpha = alpha_t.T

    counted = [
        i for i in range(N)
        if 1 <= int(trie.depth[i]) < max_depth and not bool(trie.is_leaf[i])
    ]
    return _field_aggregates(counted, k, dense_ext_to,
                             alpha, mass, src, dst, part_dev, local, n)


def _build_sharded_fn(mesh, trie: TrieArrays, depth_cap: int,
                      bps: int, block_n: int, block_e: int,
                      n_local_pad: int, exchange: str = "psum",
                      n_shards: int = 1, round_cap: Tuple[int, ...] = (),
                      *, n: int, m: int, k: int, dense_ext_to: bool = True,
                      identity: bool = True):
    """The whole sharded field as one jitted program (module docstring
    §sharded): the ``shard_map``'d halo-exchange depth loop, the reorder of
    ``alpha`` to vertex order, the slot-to-edge mass scatter and the
    aggregates.  Nothing crosses to the host inside a call.

    Static per (mesh, trie topology, packing shapes, exchange backend,
    graph size): the trie topology and depth count bake into the loop;
    probabilities, the partition vector and the packed shard arrays arrive
    as runtime inputs.  The ``exchange`` backend decides the per-depth
    collective: one ``psum`` of the union frontier (``fr_a``/``fr_b`` = the
    union owner maps), or the two-tier sliced exchange — a small ``psum``
    of the hot broadcast rows (``fr_a``/``fr_b`` = the hot owner maps) plus
    ``S - 1`` ring ``ppermute`` rounds of the cold per-shard-pair slices
    (``send`` = the ``send_local`` tables, round ``r`` padded to the static
    ``round_cap[r]``; ``src_map`` is then the packing's sliced variant).
    Each shard keeps its states feature-major (``(N, n_local_pad)``), the
    ``vm_step`` kernel's layout, so the exchanged rows of the module
    docstring travel as columns.

    After the loop the shard outputs are gathered (``all-gather``) and the
    rest runs alike on every device, so the program's only ``all-reduce``
    and ``collective-permute`` ops are the halo exchange's.  The program is
    named ``sharded_field_fn``: its XLA module name holds ``field_fn``, as
    the fused jnp program's does.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.kernels.vm_step.kernel import vm_step_packed

    depth = trie.depth.copy()
    labels_n = trie.label.copy()
    N = trie.n_nodes
    max_depth = min(trie.max_depth, depth_cap)
    sliced = exchange == "sliced"
    counted = [
        i for i in range(N)
        if 1 <= int(depth[i]) < max_depth and not bool(trie.is_leaf[i])
    ]

    def body(meta, src_map, dst_local, dst_label, inv_full, src_g, dst_g,
             vlab, fr_a, fr_b, send, part, p, lab_vcount, T, Tsum):
        # sharded inputs arrive with their leading shard axis (size 1)
        (meta, src_map, dst_local, dst_label, inv_full, src_g, dst_g,
         vlab, fr_a, fr_b, send) = (
            x[0] for x in (meta, src_map, dst_local, dst_label, inv_full,
                           src_g, dst_g, vlab, fr_a, fr_b, send))
        local = (part[src_g] == part[dst_g]).astype(jnp.float32)
        inv_local = inv_full * local
        tsum_t = Tsum.T
        alpha_t = _prior_columns(depth, labels_n, N, vlab, lab_vcount, p,
                                 n_local_pad).T
        beta_t = alpha_t
        slot_mass = jnp.zeros(inv_full.shape, dtype=jnp.float32)
        for _ in range(2, max_depth + 1):
            if sliced:
                # two-tier exchange: psum the (small) hot broadcast rows,
                # then ring-exchange the cold per-pair slices — round r
                # ships each shard's slice for the reader r hops ahead,
                # padded to that round's own largest pair
                hot = jax.lax.psum(beta_t[:, fr_a] * fr_b[None, :], "model")
                me = jax.lax.axis_index("model")
                parts = [hot]
                for r in range(1, n_shards):
                    reader = jax.lax.rem(me + r, n_shards)
                    rows = jax.lax.dynamic_index_in_dim(
                        send, reader, axis=0, keepdims=False)
                    payload = beta_t[:, rows[: round_cap[r]]]
                    parts.append(jax.lax.ppermute(
                        payload, "model",
                        perm=[(i, (i + r) % n_shards)
                              for i in range(n_shards)]))
                fr = jnp.concatenate(parts, axis=1)
            else:
                # union exchange: each shard contributes its owned frontier
                # rows (fr_a = fr_local_idx, fr_b = fr_owned); psum
                # completes the union (each row has exactly one owner)
                fr = jax.lax.psum(beta_t[:, fr_a] * fr_b[None, :], "model")
            a_src_t = jnp.take(jnp.concatenate([beta_t, fr], axis=1),
                               src_map, axis=1)
            # per-slot mass over ALL edges (cut + local) at this depth
            slot_mass = slot_mass + (
                a_src_t * jnp.take(tsum_t, dst_label, axis=1)
            ).sum(axis=0) * inv_full
            # the DP advances over intra-partition edges only
            beta_t = vm_step_packed(
                a_src_t, T, dst_local, dst_label, inv_local, meta.T,
                bps, block_n, block_e)
            alpha_t = alpha_t + beta_t
        return alpha_t.T[None], slot_mass[None]

    sharded = (P("model"),) * 11
    loop = jax.shard_map(
        body, mesh=mesh,
        in_specs=sharded + (P(), P(), P(), P(), P()),
        out_specs=(P("model"), P("model")),
        check_vma=False,
    )
    everywhere = NamedSharding(mesh, P())

    def gathered(x, shape):
        return jax.lax.with_sharding_constraint(jnp.reshape(x, shape),
                                                everywhere)

    def sharded_field_fn(meta, src_map, dst_local, dst_label, inv_cnt, src_g,
                         dst_g, vlab, slot_raw, fr_a, fr_b, send, pos, part,
                         p, lab_vcount, T, Tsum):
        alpha_sh, slot_mass = loop(
            meta, src_map, dst_local, dst_label, inv_cnt, src_g, dst_g, vlab,
            fr_a, fr_b, send, part, p, lab_vcount, T, Tsum)
        alpha_pos = gathered(alpha_sh, (n_shards * n_local_pad, N))
        # kernel rows are positions; the shard map's inverse restores
        # vertex order (a slice under the identity stripe map)
        alpha = alpha_pos[:n] if identity else alpha_pos[pos]
        # the aggregates run over the slots, in slot order (padding slots
        # carry no mass); the edge masses scatter into raw edge order
        slots = (n_shards * inv_cnt.shape[1],)
        slot_mass = gathered(slot_mass, slots)
        src_g, dst_g, raw = (gathered(x, slots)
                             for x in (src_g, dst_g, slot_raw))
        mass = jnp.zeros((m,), jnp.float32).at[
            jnp.where(raw < 0, m, raw)].set(slot_mass, mode="drop")
        local = (part[src_g] == part[dst_g]).astype(jnp.float32)
        out = _field_aggregates(counted, k, dense_ext_to, alpha, slot_mass,
                                src_g, dst_g, part, local, n)
        return (out[0], out[1], mass) + out[3:]

    return jax.jit(sharded_field_fn)


def _field_mesh(pre: Dict):
    """The sharded field's mesh: ``pre["_mesh"]`` when a caller seeded one,
    else ``repro.launch.mesh.make_smoke_mesh()`` over every visible device,
    cached there."""
    mesh = pre.get("_mesh")
    if mesh is None:
        from repro.launch.mesh import make_smoke_mesh

        mesh = make_smoke_mesh()
        pre["_mesh"] = mesh
    return mesh


def _put_rows(arr, host: np.ndarray, rows) -> object:
    """``arr`` (sharded along its leading axis) with the leading-axis rows
    in ``rows`` replaced by those of ``host``: only those rows' buffers
    are uploaded, every other device buffer is kept as it is."""
    bufs = []
    for shard in arr.addressable_shards:
        lo = shard.index[0].start or 0
        hi = shard.index[0].stop if shard.index[0].stop is not None \
            else host.shape[0]
        if any(lo <= r < hi for r in rows):
            bufs.append(jax.device_put(host[lo:hi], shard.device))
        else:
            bufs.append(shard.data)
    return jax.make_array_from_single_device_arrays(
        arr.shape, arr.sharding, bufs)


def _sharded_device_arrays(sp, pre: Dict, mesh=None) -> Dict:
    """Device-resident stacked shard arrays, re-uploaded per dirty shard.

    Each ``(S, ...)`` array is placed on the field's mesh, one shard slice
    per device along ``model`` (the shard map's inverse permutation
    ``pos`` is replicated).  The packing's ``shard_epoch`` counters say
    which shard slices changed since this cache last uploaded them; only
    those rows move to the device (plus the small frontier maps when
    ``fr_epoch`` moved).  ``raw_epoch`` says which shards' ``slot_raw``
    followed an edge renumbering; that row alone re-uploads.  Upload counts
    accumulate in ``pre["_shard_uploads"]`` (``last_shards``: shard slices
    uploaded by the latest sync) for benchmarks/tests.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _field_mesh(pre) if mesh is None else mesh
    by_shard = NamedSharding(mesh, P("model"))
    everywhere = NamedSharding(mesh, P())
    stats = pre.setdefault(
        "_shard_uploads", {"last_shards": 0, "total_shards": 0, "rebuilds": 0})
    names = ("meta", "src_map", "src_map_sliced", "dst_local", "dst_label",
             "inv_cnt", "src_global", "dst_global", "vlabels", "send_local")

    def host(nm):
        a = getattr(sp, nm)
        return a.astype(np.int32) if nm == "slot_raw" else a

    def put(a):
        return jax.device_put(a, by_shard)

    def pos():
        return (None if sp.identity
                else jax.device_put(sp.pos_of.astype(np.int32), everywhere))

    sdev = pre.get("_shard_dev")
    if sdev is not None and (sdev["sp"] is not sp or sdev["mesh"] is not mesh):
        sdev = None  # packing rebuilt from scratch (capacity overflow, a
        #              re-deal) or another mesh
    if sdev is None:
        sdev = {"sp": sp, "mesh": mesh,
                "epochs": sp.shard_epoch.copy(),
                "raw_epochs": sp.raw_epoch.copy(),
                "fr_epoch": sp.fr_epoch,
                "version": sp.version,
                "arrays": {nm: put(host(nm)) for nm in names + ("slot_raw",)},
                "fr": (put(sp.fr_local_idx), put(sp.fr_owned)),
                "hot": (put(sp.hot_local_idx), put(sp.hot_owned)),
                "n_pos": sp.pos_of.shape[0],
                "pos": pos()}
        pre["_shard_dev"] = sdev
        stats["last_shards"] = sp.n_shards
        stats["total_shards"] += sp.n_shards
        stats["rebuilds"] += 1
        return sdev
    dirty = np.nonzero(sp.shard_epoch != sdev["epochs"])[0].tolist()
    raw_dirty = np.nonzero(sp.raw_epoch != sdev["raw_epochs"])[0].tolist()
    arrays = sdev["arrays"]
    if dirty:
        for nm in names:
            arrays[nm] = _put_rows(arrays[nm], host(nm), dirty)
    if dirty or raw_dirty:
        arrays["slot_raw"] = _put_rows(arrays["slot_raw"], host("slot_raw"),
                                       sorted(set(dirty) | set(raw_dirty)))
    if sp.fr_epoch != sdev["fr_epoch"]:
        sdev["fr"] = (put(sp.fr_local_idx), put(sp.fr_owned))
        sdev["fr_epoch"] = sp.fr_epoch
    if sp.pos_of.shape[0] != sdev["n_pos"]:
        # vertex growth extended the shard map's identity tail
        sdev["n_pos"] = sp.pos_of.shape[0]
        sdev["pos"] = pos()
    sdev["epochs"] = sp.shard_epoch.copy()
    sdev["raw_epochs"] = sp.raw_epoch.copy()
    sdev["version"] = sp.version
    uploaded = len(set(dirty) | set(raw_dirty))
    stats["last_shards"] = uploaded
    stats["total_shards"] += uploaded
    return sdev


def _pallas_sharded_field(
    g: LabelledGraph,
    trie: TrieArrays,
    part: np.ndarray,
    k: int,
    depth_cap: int,
    pre: Dict,
    dense_ext_to: bool,
    mesh=None,
    shard_map_source: str = "stripe",
    halo_exchange: str = "sliced",
    parent: Span = NOOP_SPAN,
):
    """Multi-device extroversion field: ``vm_step`` per shard over the
    graph's sharded packing, halo-exchanging only the ``beta`` rows other
    shards read between depth steps (module docstring §sharded).  One
    jitted program (:func:`_build_sharded_fn`) per call; its outputs stay
    on the device.

    The mesh defaults to ``repro.launch.mesh.make_smoke_mesh()`` over every
    visible device and is cached in ``pre["_mesh"]``; callers may seed
    ``pre["_mesh"]`` (e.g. a production mesh's ``model`` axis) instead.

    The shard map is sticky: the first sharded evaluation resolves
    ``shard_map_source`` (``"stripe"`` | ``"partition"`` — dealt along this
    call's partition vector — | ``"bfs"``) into a vertex permutation cached
    in ``pre["_shard_order"]``; subsequent calls reuse it so the packing is
    never re-dealt mid-invocation.  ``Taper.maybe_redeal_shards`` (called
    by ``OnlineTaper.commit_invocation``) refreshes it off the critical
    path.  Callers may seed ``pre["_shard_order"] = (token, pos_of)``
    directly (``Taper.seed_shard_order`` does, from a given partition;
    tests use random permutations).

    When the packing or its device copy has to change (first call, a
    mutation, a re-deal), ``parent`` receives a ``field.shard_pack`` child
    around the packing and the upload, with ``rebuilt`` (1 when the device
    copy was rebuilt whole) and ``shards_uploaded``.
    """
    from repro.graphs.sharded_packing import compute_shard_order
    from repro.kernels.vm_step.kernel import MAX_EDGE_BLOCKS

    mesh = _field_mesh(pre) if mesh is None else mesh
    S = int(mesh.shape["model"])

    n, m = g.n, g.m
    N = trie.n_nodes
    lab_vcount = pre.get("lab_vcount")
    if lab_vcount is None:
        lab_vcount = g.label_counts()

    order_entry = pre.get("_shard_order")
    if order_entry is None and shard_map_source != "stripe":
        order_entry = (f"{shard_map_source}:0",
                       compute_shard_order(g, shard_map_source, S, part=part))
        pre["_shard_order"] = order_entry
    token, order = order_entry if order_entry is not None else ("stripe", None)

    sdev = pre.get("_shard_dev")
    if (sdev is not None and sdev["version"] == g.version
            and sdev["sp"].order_token == token and sdev["mesh"] is mesh):
        sp = sdev["sp"]
    else:
        with parent.child("field.shard_pack") as pack:
            rebuilds = pre.get("_shard_uploads", {}).get("rebuilds", 0)
            cnt = pre.get("cnt")
            if cnt is None:
                # the graph's own (incrementally patched) matrix, so the
                # cached sharded packing stays patchable across mutations
                cnt = g.cached_neighbor_label_counts()
            sp = g.vm_packing_sharded(S, cnt=cnt, order=order,
                                      order_token=token)
            if sp.eb_cap > MAX_EDGE_BLOCKS:
                raise ValueError(
                    f"a shard of the sharded field needs {sp.eb_cap} edge "
                    f"blocks, more than the vm_step kernel's block table "
                    f"holds ({MAX_EDGE_BLOCKS}); use more shards")
            sdev = _sharded_device_arrays(sp, pre, mesh)
            ups = pre["_shard_uploads"]
            pack.set(rebuilt=int(ups["rebuilds"] != rebuilds),
                     shards_uploaded=int(ups["last_shards"]))
    arr = sdev["arrays"]

    T = _capped_transition(trie, depth_cap)
    Tsum = T.sum(axis=2)
    round_cap = tuple(int(c) for c in sp.round_cap)
    key = ("sharded", trie.topology_signature(), int(depth_cap), S,
           sp.blocks_per_shard, sp.block_n, sp.block_e, sp.eb_cap,
           sp.n_local_pad, sp.h_pad, sp.hot_pad, round_cap, halo_exchange,
           id(mesh), n, m, k, dense_ext_to, sp.identity)
    fn = _FIELD_CACHE.get(key)
    if fn is None:
        fn = _build_sharded_fn(
            mesh, trie, depth_cap, sp.blocks_per_shard, sp.block_n,
            sp.block_e, sp.n_local_pad, exchange=halo_exchange,
            n_shards=S, round_cap=round_cap, n=n, m=m, k=k,
            dense_ext_to=dense_ext_to, identity=sp.identity)
        while len(_FIELD_CACHE) >= 64:
            _FIELD_CACHE.pop(next(iter(_FIELD_CACHE)))
        _FIELD_CACHE[key] = fn

    if halo_exchange == "sliced":
        src_map_in = arr["src_map_sliced"]
        fr_a, fr_b = sdev["hot"]
    else:
        src_map_in, (fr_a, fr_b) = arr["src_map"], sdev["fr"]
    out = fn(arr["meta"], src_map_in, arr["dst_local"], arr["dst_label"],
             arr["inv_cnt"], arr["src_global"], arr["dst_global"],
             arr["vlabels"], arr["slot_raw"], fr_a, fr_b, arr["send_local"],
             sdev["pos"], part.astype(np.int32), np.asarray(trie.p),
             np.asarray(lab_vcount), T, Tsum)

    full = sp.full_field_bytes_per_depth(n, N)
    halo = sp.halo_bytes_per_depth(N, exchange=halo_exchange)
    max_depth = min(trie.max_depth, depth_cap)
    pre["_halo_stats"] = {
        "halo_bytes_per_depth": halo,
        "full_field_bytes_per_depth": full,
        "halo_ratio": halo / max(full, 1),
        "shard_map_source": token.split(":")[0],
        "halo_exchange": halo_exchange,
        "n_shards": S,
        # devices the field program's outputs span (a mesh of one device
        # repeated, or inputs pinned to one chip, would show here)
        "n_devices": len(out[0].sharding.device_set),
        "n_frontier": sp.n_frontier,
        "hot_rows": sp.hot_pad,
        "sliced_rows": sp.hot_pad + int(sp.round_cap[1:].sum()),
        # DP depth steps the kernel ran (each one is a halo exchange)
        "depth_steps": max(int(max_depth) - 1, 0),
    }
    return out


def extroversion_field(
    g: LabelledGraph,
    trie: TrieArrays,
    part: np.ndarray,
    k: int,
    depth_cap: Optional[int] = None,
    _precomputed: Optional[Dict] = None,
    fused: bool = True,
    dense_ext_to: bool = True,
    backend: str = "jnp",
    shard_map_source: str = "stripe",
    halo_exchange: str = "sliced",
    parent: Optional[Span] = None,
) -> ExtroversionResult:
    """Compute the extroversion field of ``part`` under the workload trie.

    ``depth_cap`` implements the paper's §5.2.2 time heuristic (stop VM row
    expansion at path length < t, trading accuracy for time).

    ``dense_ext_to=True`` (the default, matching ``TaperConfig``) also
    returns the dense ``(n, k)`` per-destination external-mass matrix in one
    fused pass — one extra ``segment_sum`` and ``n*k`` floats of memory.
    ``dense_ext_to=False`` selects the two-phase §Perf-T2 trade-off: the
    field pass skips the matrix and the swap engine derives each
    *candidate's* destination preferences lazily from its own cut edges —
    cheaper when ``k`` is large or candidate queues are short, at the cost
    of a little host work per candidate.

    ``backend`` selects the DP engine: ``"jnp"`` (the fused XLA
    transcription), ``"pallas"`` (the ``vm_step`` TPU kernel over the
    graph's cached edge packing; interpret mode auto-disables on TPU) or
    ``"pallas_sharded"`` (the same kernel per shard over every visible
    device, halo-exchanging only the cross-shard ``beta`` rows between
    depth steps — see the module docstring; seed ``_precomputed["_mesh"]``
    to pin a specific mesh).  ``shard_map_source`` / ``halo_exchange``
    apply to the sharded backend only: how vertices are dealt to shards
    (``"stripe"`` | ``"partition"`` | ``"bfs"``) and whether the exchange
    moves per-shard-pair slices (``"sliced"``: a psum'd hot union plus
    ``S - 1`` ring ``ppermute`` rounds, padded per round) or the psum'd
    union frontier (``"psum"``).

    ``parent`` (optional span) receives a ``field.fetch`` child around the
    device-to-host copy of the outputs, with their total ``bytes``; the
    device finishes before it opens, so it times the copy alone.
    """
    depth_cap = depth_cap or trie.max_depth
    pre = _precomputed if _precomputed is not None else {}
    if backend == "pallas":
        out = _pallas_field(g, trie, part, k, depth_cap, pre, dense_ext_to)
    elif backend == "pallas_sharded":
        out = _pallas_sharded_field(g, trie, part, k, depth_cap, pre,
                                    dense_ext_to,
                                    shard_map_source=shard_map_source,
                                    halo_exchange=halo_exchange,
                                    parent=NOOP_SPAN if parent is None
                                    else parent)
    elif backend == "jnp":
        key = (trie.topology_signature(), k, depth_cap, g.n, g.m, fused,
               dense_ext_to)
        fn = _FIELD_CACHE.get(key)
        if fn is None:
            fn = _build_field_fn(key, trie, k, depth_cap, fused=fused,
                                 dense_ext_to=dense_ext_to)
            _FIELD_CACHE[key] = fn

        cnt = pre.get("cnt")
        if cnt is None:
            cnt = g.neighbor_label_counts()
        lab_vcount = pre.get("lab_vcount")
        if lab_vcount is None:
            lab_vcount = g.label_counts()
        dev = _device_inputs(g, pre, cnt, lab_vcount)
        args = (dev["src"], dev["dst"], dev["labels"], dev["cnt"],
                dev["lab_vcount"], jnp.asarray(part.astype(np.int32)),
                jnp.asarray(trie.p), jnp.asarray(trie.cond_p))
        if fused:
            o = _dst_order_inputs(g, dev)
            out = fn(*args, o["src"], o["dst"], o["rank"], o["in_ptr"],
                     o["row_ptr"], n=g.n, m=g.m, levels=o["levels"])
        else:
            out = fn(*args, n=g.n, m=g.m)
    else:
        raise ValueError(f"unknown field backend {backend!r}")
    parent = NOOP_SPAN if parent is None else parent
    if parent is not NOOP_SPAN:
        # np.asarray waits anyway; waiting first keeps the device's tail
        # out of the fetch span
        jax.block_until_ready(out)
    with parent.child("field.fetch") as fetch:
        host = [np.asarray(a) for a in out]
        fetch.set(bytes=sum(a.nbytes for a in host))
    if dense_ext_to:
        alpha, pr, mass, extro_mass, extroversion, ext_to = host
    else:
        alpha, pr, mass, extro_mass, extroversion = host
        ext_to = None
    return ExtroversionResult(
        alpha=alpha,
        pr=pr,
        edge_mass=mass,
        extro_mass=extro_mass,
        extroversion=extroversion,
        ext_to=ext_to,
        total_extroversion=float(extro_mass.sum()),
    )


# ---------------------------------------------------------------------------
# Reference single-cell evaluation (paper §4.2) — used by tests/examples
# ---------------------------------------------------------------------------


def vm_cell(
    g: LabelledGraph, trie: TrieArrays, path_vertices, label_names=None
) -> np.ndarray:
    """``VM^(t)[p_1, ..., p_{t-1}, *]``: the distribution over next vertices
    given the path ``path_vertices`` (paper §4.2 worked example).

    Returns an ``(n,)`` vector of transition probabilities (rows need not sum
    to 1; the shortfall is the 'no subsequent traversal' mass, §4.2 fn. 6).
    """
    path = list(path_vertices)
    # find trie node for the label string of the path
    node = 0
    for v in path:
        child = trie.child_index[node, g.labels[v]]
        if child < 0:
            return np.zeros(g.n, dtype=np.float64)
        node = int(child)
    last = path[-1]
    nbrs = g.neighbors(last)
    nbr_labels = g.labels[nbrs]
    out = np.zeros(g.n, dtype=np.float64)
    for lab_id in range(trie.n_labels):
        child = trie.child_index[node, lab_id]
        if child < 0:
            continue
        cond = float(trie.cond_p[child])
        same = nbrs[nbr_labels == lab_id]
        if same.size:
            out[same] += cond / same.size
    return out
