"""Vertex swapping (paper §3.1, §5.5) — batched numpy prepare, scalar walk.

Per TAPER internal iteration:

1. take each partition's most extroverted vertices, in descending
   extroversion order (NOT random boundary vertices — paper §3.1);
2. for each candidate, compute its preferred destination partitions (by
   external mass, descending) and its *family* — the flood-fill closure of
   local vertices whose traversal probability toward a member is "more
   likely than not" (paper §5.5);
3. cooperative offer/receive: the receiving partition accepts only when its
   introversion gain exceeds the sender's loss; otherwise try the next
   destination (paper §5.5, Fig. 6);
4. a vertex moves at most once per iteration; a 5% balance constraint is
   enforced (paper §6.2.1).

All probability masses come precomputed from the extroversion field (the jit
DP).  The seed implementation walked each family one neighbour at a time with
an ``np.searchsorted`` reverse-edge lookup per neighbour pair; this version
keeps the offer/receive semantics and balance constraint bit-identical (see
``repro.core.swap_ref`` + tests/test_swap_parity.py) but moves the lookups
into whole-iteration precomputes:

* the *joinable* edge relation ``join_e`` (``dst`` may join ``src``'s
  family) is one vectorised test over all edges at iteration start.  For an
  unmoved candidate every family member sits in its iteration-start
  partition, so the live membership test reduces to ``join_e[e]`` and "the
  neighbour has not moved";
* singleton candidates (most of them) get their k-destination gain and
  preference rows from batched ``bincount``/``argsort`` passes;
* multi-member families (2–3 members on average) are flood-filled and
  their gains toward all ``k`` partitions summed in scalar Python over the
  walk's host lists, in CSR edge order — the float64 additions of
  ``np.bincount``, in the same order, so the same bits.  The fill expands
  a hub over only its ``max_scan_neighbors`` highest-``rel_mass_out``
  edges, in the seed guard's ``argsort`` order.

Internal iterations are therefore "inexpensive" in the paper's sense (§5).

Given a parent span, an iteration traces its two phases as children:
``swap.prepare`` (candidate queue, whole-iteration precomputes, batched
singleton rows) and ``swap.walk`` (the sequential offer/receive walk),
which ends with the walk's counters of :class:`SwapStats` as attributes.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field as dfield
from typing import List, Optional, Tuple

import numpy as np

from repro.core.visitor import ExtroversionResult
from repro.graphs.graph import LabelledGraph
from repro.obs.trace import NOOP_SPAN, Span
from repro.utils import get_logger

log = get_logger("core.swap")


@dataclass
class SwapConfig:
    candidates_per_part: Optional[int] = None  # None = drain the whole queue (§5.5)
    family_threshold: float = 0.5     # "more likely than not"
    family_max_size: int = 12
    balance_eps: float = 0.05         # paper: max 5% imbalance
    min_gain: float = 0.0
    safe_introversion: float = 0.95   # §5.2.1 safe-vertex threshold
    max_scan_neighbors: int = 512     # hub guard in family flood fill
    rank_by: str = "extroversion"     # "extroversion" (paper §3.2 ratio) or
                                      # "mass" (absolute external mass; beyond-paper)


@dataclass
class SwapStats:
    moves: int
    accepted_offers: int
    rejected_offers: int
    candidates: int
    # the walk's work, by path (not compared: they count how the engine
    # reached its decisions, which the seed engine does differently)
    singles_visited: int = dfield(default=0, compare=False)
    # singleton candidates whose batched row was re-derived because a
    # vertex of their 1-hop neighbourhood had moved
    stale_rows: int = dfield(default=0, compare=False)
    family_walks: int = dfield(default=0, compare=False)
    family_members: int = dfield(default=0, compare=False)
    # perf_counter seconds spent in the family branch
    family_s: float = dfield(default=0.0, compare=False)


def _concat_csr_edges(
    g: LabelledGraph, vs: np.ndarray
) -> Tuple[np.ndarray, np.ndarray]:
    """``LabelledGraph.edge_indices_of`` plus the per-vertex edge counts."""
    cnts = g.row_ptr[vs + 1] - g.row_ptr[vs]
    return g.edge_indices_of(vs), cnts


def _family_scalar(
    v: int, rp: list, dl: list, join: bytes, moved: bytearray,
    rel_mass_out: np.ndarray, max_size: int, cap: int,
) -> List[int]:
    """Flood-fill family of an unmoved ``v``: local vertices likely
    (> threshold) to traverse *to* a current member (paper §5.5), in scalar
    Python over the walk's host lists.  Edge ``j = (w, u)`` of a member
    ``w`` recruits ``u`` when ``join[j]`` and ``u`` has not moved; members
    are scanned breadth-first, each one's edges in CSR order, so members
    join in the seed's first-occurrence order, truncated at ``max_size``.
    A member with more than ``cap`` edges (a hub) is scanned over only its
    ``cap`` highest-``rel_mass_out`` edges, in the seed guard's ``argsort``
    order (``rel_mass_out[e]`` = probability that a traversal out of
    ``src[e]`` follows ``e``)."""
    fam = [v]
    for w in fam:  # breadth-first: the loop also visits appended members
        if len(fam) >= max_size:
            break
        lo, hi = rp[w], rp[w + 1]
        if hi - lo > cap:
            edges = (lo + np.argsort(-rel_mass_out[lo:hi])[:cap]).tolist()
        else:
            edges = range(lo, hi)
        for j in edges:
            if join[j]:
                u = dl[j]
                if not moved[u] and u not in fam:
                    fam.append(u)
                    if len(fam) >= max_size:
                        return fam
    return fam


def _family_gains_scalar(
    fam: List[int], rp: list, dl: list, part: np.ndarray,
    sym_mass: np.ndarray, k: int,
) -> List[float]:
    """``(k,)`` traversal mass between the family and *each* partition
    (both edge directions, ``sym_mass``), summed over the members' edges in
    CSR order: the float64 additions of a ``np.bincount`` over the same
    edges, in the same order.

    ``gains[dest]`` is the receiver gain of moving the family to ``dest``;
    ``gains[home]`` is the sender loss.  Family-internal edges move with the
    family and edges to third partitions stay cut, so neither affects the
    decision.  (The seed recomputed this with Python loops once per
    destination attempt.)"""
    gains = [0.0] * k
    for w in fam:
        for j in range(rp[w], rp[w + 1]):
            u = dl[j]
            if u not in fam:
                gains[part[u]] += sym_mass[j]
    return gains


def _candidate_queue(
    part: np.ndarray,
    field: ExtroversionResult,
    k: int,
    cfg: SwapConfig,
    candidate_mask: Optional[np.ndarray] = None,
) -> np.ndarray:
    """Most extroverted vertices per partition (safe ones skipped, §5.2.1),
    merged into one globally descending queue (paper §3.1).

    ``candidate_mask`` restricts the queue to a vertex subset — the dirty
    frontier of mutated vertices for mutation-local online invocations
    (paper §5.5's queue pruning generalised to topology deltas)."""
    ext = field.extroversion if cfg.rank_by == "extroversion" else field.extro_mass
    per_part: List[np.ndarray] = []
    for p in range(k):
        members = np.nonzero(part == p)[0]
        if candidate_mask is not None and members.size:
            members = members[candidate_mask[members]]
        if members.size == 0:
            continue
        # §5.2.1: vertices with introversion above the safe threshold are
        # discarded (they cannot be good swap candidates)
        unsafe = field.extroversion[members] > (1.0 - cfg.safe_introversion)
        members = members[unsafe]
        if members.size == 0:
            continue
        top = members[np.argsort(-ext[members])]
        if cfg.candidates_per_part is not None:
            top = top[: cfg.candidates_per_part]
        per_part.append(top.astype(np.int64))
    if not per_part:
        return np.empty(0, dtype=np.int64)
    candidates = np.concatenate(per_part)
    # stable sort keeps the per-partition order on ties, like the seed's
    # Python list.sort(key=-ext)
    return candidates[np.argsort(-ext[candidates], kind="stable")]


def _lazy_prefs(
    g: LabelledGraph, v: int, home: int, part: np.ndarray,
    field: ExtroversionResult, k: int
) -> np.ndarray:
    """Two-phase path (§Perf-T2): per-destination preference computed lazily
    from the candidate's own cut edges.  The ``bincount`` accumulates the
    float32 masses into float64 in edge order — the same arithmetic as the
    seed's ``np.add.at``."""
    lo, hi = int(g.row_ptr[v]), int(g.row_ptr[v + 1])
    pn = part[g.dst[lo:hi]]
    cut = pn != home
    # .astype: bincount of an empty input yields int64 zeros
    return np.bincount(
        pn[cut],
        weights=field.edge_mass[lo:hi][cut].astype(np.float64),
        minlength=k).astype(np.float64)


def swap_iteration(
    g: LabelledGraph,
    part: np.ndarray,
    field: ExtroversionResult,
    k: int,
    cfg: SwapConfig,
    rng: np.random.Generator,
    candidate_mask: Optional[np.ndarray] = None,
    parent: Optional[Span] = None,
) -> Tuple[np.ndarray, SwapStats]:
    """One internal TAPER iteration of offer/receive vertex swapping.

    ``candidate_mask`` (optional ``(n,)`` bool) seeds the candidate queue
    from a vertex subset only — used by ``OnlineTaper`` to run
    mutation-local invocations over the dirty frontier; ``None`` keeps the
    full paper §3.1 queue.  ``parent`` (optional span) receives the
    ``swap.prepare`` and ``swap.walk`` child spans (module doc).

    Produces bit-identical partitions and stats to the seed implementation
    (``repro.core.swap_ref.swap_iteration_reference``), but amortises almost
    all per-candidate work into whole-array precomputes:

    * the *joinable* relation (which neighbour can ever enter a family) only
      shrinks during an iteration — vertices leave it by being moved, and
      ``part`` changes only for moved vertices — so a candidate whose family
      is a singleton at iteration start stays a singleton.  Singleton
      candidates (the vast majority under the 0.5 "more likely than not"
      threshold) get their k-destination gain rows and preference rows from
      two batched ``bincount``/``argsort`` passes over the whole candidate
      set;
    * the sequential offer/receive walk then runs in plain Python over those
      precomputed rows; a batched row is re-derived per candidate only when
      a vertex in its 1-hop neighbourhood has moved since the batch (the
      gains/prefs of v depend only on ``part``/``moved`` over N(v) ∪ {v});
    * candidates with multi-member families are flood-filled on live state
      by ``_family_scalar``: a member ``u`` of an unmoved candidate's family
      is itself unmoved, so ``part[u]`` is its iteration-start partition and
      the seed's test (same partition, unmoved, reverse-edge traversal
      probability above the threshold) is ``join_e[e]`` and not ``moved[u]``.
      Their gains come from ``_family_gains_scalar``.
    """
    parent = NOOP_SPAN if parent is None else parent
    prepare = parent.child("swap.prepare")
    part = part.astype(np.int32).copy()
    n = g.n
    sizes = np.bincount(part, minlength=k).astype(np.int64)
    ideal = n / k
    max_size = int(np.floor((1.0 + cfg.balance_eps) * ideal))
    min_size = int(np.ceil((1.0 - cfg.balance_eps) * ideal))

    pr_src = np.maximum(field.pr[g.src], 1e-30)
    rel_mass_out = field.edge_mass / pr_src
    rev = g.reverse_edge_index
    rev_ok = rev >= 0
    rev_c = np.maximum(rev, 0)

    candidates = _candidate_queue(part, field, k, cfg, candidate_mask)
    stats = SwapStats(0, 0, 0, int(candidates.size))
    if candidates.size == 0:
        prepare.end()
        return part, stats

    # ---- whole-iteration precomputes --------------------------------------
    # symmetric edge mass m_out + m_in, in the seed's float64 arithmetic
    sym_mass = field.edge_mass.astype(np.float64) + np.where(
        rev_ok, field.edge_mass[rev_c].astype(np.float64), 0.0)
    # rel_mass_out of the reverse edge (u -> w traversal for edge e=(w, u))
    rel_rev = np.where(rev_ok, rel_mass_out[rev_c], -np.inf)
    # an edge can recruit its dst into src's family ("joinable"); this set
    # only shrinks as vertices move, so it is computed once per iteration
    join_e = (part[g.src] == part[g.dst]) & (rel_rev > cfg.family_threshold)
    has_join = np.zeros(n, dtype=bool)
    has_join[g.src[join_e]] = True
    is_single = ~has_join[candidates]

    # ---- batched gain/pref rows for singleton-family candidates -----------
    S = candidates[is_single]
    row_of = np.full(candidates.size, -1, dtype=np.int64)
    row_of[is_single] = np.arange(S.size)
    dense = field.ext_to is not None
    if S.size:
        eidx, s_cnts = _concat_csr_edges(g, S)
        cid = np.repeat(np.arange(S.size, dtype=np.int64), s_cnts)
        nbr = g.dst[eidx].astype(np.int64)
        notself = nbr != np.repeat(S, s_cnts)
        e_i, c_i, n_i = eidx[notself], cid[notself], nbr[notself]
        # .astype guards: bincount of an empty input yields int64 zeros
        gains_mat = np.bincount(
            c_i * k + part[n_i], weights=sym_mass[e_i], minlength=S.size * k
        ).astype(np.float64).reshape(S.size, k)
        if dense:
            prefs_mat = field.ext_to[S].copy()
        else:
            cut = part[n_i] != part[S][c_i]
            prefs_mat = np.bincount(
                c_i[cut] * k + part[n_i[cut]],
                weights=field.edge_mass[e_i[cut]].astype(np.float64),
                minlength=S.size * k,
            ).astype(np.float64).reshape(S.size, k)
        prefs_mat[np.arange(S.size), part[S]] = -np.inf
        order_mat = np.argsort(-prefs_mat, axis=1)
        gains_rows = gains_mat.tolist()
        prefs_rows = prefs_mat.tolist()
        order_rows = order_mat.tolist()
    else:
        gains_rows = prefs_rows = order_rows = []

    # ---- sequential offer/receive walk (pure Python on cached rows) -------
    rp = g.row_ptr.tolist()
    dl = g.dst.tolist()
    join_l = join_e.tobytes()
    cand_list = candidates.tolist()
    row_list = row_of.tolist()
    single_list = is_single.tolist()
    dirty = bytearray(n)  # the moved vertices (part changed since the batch)
    sizes_l = sizes.tolist()
    min_gain = cfg.min_gain
    prepare.end()

    walk = parent.child("swap.walk")
    perf_counter = time.perf_counter
    fam_max, hub_deg = cfg.family_max_size, cfg.max_scan_neighbors
    singles = stale = walks = members = 0
    family_s = 0.0
    for ci, v in enumerate(cand_list):
        if dirty[v]:
            continue
        home = int(part[v])
        if single_list[ci]:
            singles += 1
            fresh = not dirty[v]
            if fresh:
                for j in range(rp[v], rp[v + 1]):
                    if dirty[dl[j]]:
                        fresh = False
                        break
            row = row_list[ci]
            if fresh:
                gains = gains_rows[row]
                prefs = prefs_rows[row]
                order = order_rows[row]
            else:
                stale += 1
                # 1-hop state changed: re-derive from live part[] (same
                # arithmetic as the batch).  Preference rows built from
                # ext_to are static — only the two-phase lazy prefs depend
                # on neighbours' partitions; gains re-derive lazily below,
                # only once a destination passes the balance check.
                if dense:
                    prefs = prefs_rows[row]
                    order = order_rows[row]
                else:
                    prefs_a = _lazy_prefs(g, v, home, part, field, k)
                    prefs_a[home] = -np.inf
                    order = np.argsort(-prefs_a)
                    prefs = prefs_a
                gains = None
            for dest in order:
                if prefs[dest] <= 0.0:
                    break  # no external mass toward remaining partitions
                if (sizes_l[dest] + 1 > max_size
                        or sizes_l[home] - 1 < min_size):
                    stats.rejected_offers += 1
                    continue
                if gains is None:
                    lo, hi = rp[v], rp[v + 1]
                    nbrs = g.dst[lo:hi]
                    ns = nbrs != v
                    gains = np.bincount(part[nbrs[ns]],
                                        weights=sym_mass[lo:hi][ns],
                                        minlength=k)
                if gains[dest] > gains[home] + min_gain:
                    part[v] = dest
                    dirty[v] = 1
                    sizes_l[home] -= 1
                    sizes_l[dest] += 1
                    stats.moves += 1
                    stats.accepted_offers += 1
                    break
                stats.rejected_offers += 1
            continue

        # ---- multi-member family: scalar path on live state ---------------
        t_family = perf_counter()
        walks += 1
        if dense:
            prefs_a = field.ext_to[v].copy()
        else:
            prefs_a = _lazy_prefs(g, v, home, part, field, k)
        prefs_a[home] = -np.inf
        order_a = np.argsort(-prefs_a)
        fam = _family_scalar(v, rp, dl, join_l, dirty, rel_mass_out,
                             fam_max, hub_deg)
        fs = len(fam)
        members += fs
        gains_a = None  # computed on the first destination passing balance
        for dest in order_a:
            dest = int(dest)
            if prefs_a[dest] <= 0.0:
                break
            if sizes_l[dest] + fs > max_size or sizes_l[home] - fs < min_size:
                stats.rejected_offers += 1
                continue
            if gains_a is None:
                gains_a = _family_gains_scalar(fam, rp, dl, part, sym_mass, k)
            if gains_a[dest] > gains_a[home] + min_gain:
                part[fam] = dest
                for u in fam:
                    dirty[u] = 1
                sizes_l[home] -= fs
                sizes_l[dest] += fs
                stats.moves += fs
                stats.accepted_offers += 1
                break
            stats.rejected_offers += 1
        family_s += perf_counter() - t_family
    # free the walk's Python lists inside its span (millions of objects at
    # scale), so that the two phases cover the whole iteration
    del gains_rows, prefs_rows, order_rows, rp, dl, cand_list, row_list, \
        single_list
    stats.singles_visited, stats.stale_rows = singles, stale
    stats.family_walks, stats.family_members = walks, members
    stats.family_s = family_s
    walk.end(singles_visited=singles, stale_rows=stale, family_walks=walks,
             family_members=members, family_s=family_s)
    return part, stats
