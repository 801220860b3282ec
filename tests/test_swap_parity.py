"""Parity suite: the vectorised swap engine and the sharded field vs their seeds.

The vectorised ``swap_iteration`` must produce *bit-identical*
partitions and stats to the seed per-vertex implementation
(``repro.core.swap_ref``) — same candidate order, same families, same
offer/receive decisions, same rejected-offer counts — across random labelled
graphs, both ``ext_to`` modes, and chained iterations.

The ``vm_step`` kernel field (``backend="pallas_sharded"``, here on a mesh
of one device) is held to numerical (not bit) parity with the fused jnp
program: same DP, different op order.
"""
import dataclasses

import numpy as np
import pytest

from repro.core.rpq import parse_rpq
from repro.core import swap_ref
from repro.core.swap import (SwapConfig, _family_gains_scalar, _family_scalar,
                             swap_iteration)
from repro.core.swap_ref import swap_iteration_reference
from repro.core.taper import Taper, TaperConfig
from repro.core.tpstry import TPSTry
from repro.core.visitor import extroversion_field
from repro.graphs.generators import musicbrainz_like, provgen_like
from repro.graphs.partition import hash_partition
from repro.launch.mesh import make_smoke_mesh

CASES = [
    # (seed, generator, queries, k)
    (7, provgen_like, ["Entity.Entity.Entity", "Agent.Activity.Entity"], 4),
    (3, musicbrainz_like, ["Area.Artist.(Artist|Label).Area"], 8),
    (11, provgen_like, ["Entity.Activity.Agent", "Entity.(Entity)*.Entity"], 3),
]


def _setup(seed, gen, queries, k, n=1200):
    g = gen(n, seed=seed)
    w = [(parse_rpq(q), 1.0 / len(queries)) for q in queries]
    arrays = TPSTry.from_workload(w).compile(g.label_names)
    part = hash_partition(g.n, k, seed=seed)
    return g, arrays, part


@pytest.mark.parametrize("case", CASES, ids=[f"seed{c[0]}" for c in CASES])
@pytest.mark.parametrize("dense", [True, False], ids=["dense", "two-phase"])
def test_swap_iteration_bit_identical(case, dense):
    seed, gen, queries, k = case
    g, arrays, part = _setup(seed, gen, queries, k)
    # chain three iterations so later ones start from swapped state
    for it in range(3):
        fld = extroversion_field(g, arrays, part, k, dense_ext_to=dense)
        cfg = SwapConfig()
        p_new, s_new = swap_iteration(
            g, part, fld, k, cfg, np.random.default_rng(0))
        p_ref, s_ref = swap_iteration_reference(
            g, part, fld, k, cfg, np.random.default_rng(0))
        assert (p_new == p_ref).all(), f"partition mismatch at iteration {it}"
        assert s_new == s_ref, f"stats mismatch at iteration {it}"
        if s_new.moves == 0:
            break
        part = p_new


def test_swap_iteration_bit_identical_nondefault_config():
    """Capped queues, tighter balance, small families, mass ranking."""
    g, arrays, part = _setup(5, provgen_like, ["Entity.Activity.Agent"], 5)
    fld = extroversion_field(g, arrays, part, 5, dense_ext_to=True)
    cfg = SwapConfig(candidates_per_part=40, balance_eps=0.02,
                     family_max_size=4, min_gain=1e-6, rank_by="mass",
                     max_scan_neighbors=8)
    p_new, s_new = swap_iteration(g, part, fld, 5, cfg, np.random.default_rng(0))
    p_ref, s_ref = swap_iteration_reference(
        g, part, fld, 5, cfg, np.random.default_rng(0))
    assert (p_new == p_ref).all()
    assert s_new == s_ref


def _jittered(fld, seed):
    """``fld`` with each edge's mass scaled by its own factor in [0.5, 1.5):
    a vertex's edges then differ in ``rel_mass_out``, so the hub guard's
    order is not the CSR order its ties would give."""
    f = np.random.default_rng(seed).uniform(0.5, 1.5, fld.edge_mass.size)
    return dataclasses.replace(
        fld, edge_mass=(fld.edge_mass * f).astype(fld.edge_mass.dtype))


FAMILY_CASES = {
    # a loose threshold makes many multi-member families; no vertex has more
    # edges than the default hub guard
    "loose": (7, 4, SwapConfig(family_threshold=0.3)),
    # families truncated at three members
    "loose-capped": (11, 3, SwapConfig(family_threshold=0.3,
                                       family_max_size=3)),
    # a hub guard of 8 edges: the fill scans a hub's 8 likeliest edges
    # only, on jittered edge masses
    "hub-guard": (7, 4, SwapConfig(family_threshold=0.3,
                                   max_scan_neighbors=8)),
}


@pytest.mark.parametrize("case", list(FAMILY_CASES))
@pytest.mark.parametrize("dense", [True, False], ids=["dense", "two-phase"])
def test_family_paths_bit_identical(case, dense):
    """Multi-member families, with and without hubs under the guard, give
    the seed's partitions and stats over chained iterations."""
    seed, k, cfg = FAMILY_CASES[case]
    g, arrays, part = _setup(seed, provgen_like, CASES[0][2], k)
    walks = 0
    for it in range(3):
        fld = extroversion_field(g, arrays, part, k, dense_ext_to=dense)
        if case == "hub-guard":
            fld = _jittered(fld, it)
        p_new, s_new = swap_iteration(
            g, part, fld, k, cfg, np.random.default_rng(0))
        p_ref, s_ref = swap_iteration_reference(
            g, part, fld, k, cfg, np.random.default_rng(0))
        assert (p_new == p_ref).all(), f"partition mismatch at iteration {it}"
        assert s_new == s_ref, f"stats mismatch at iteration {it}"
        walks += s_new.family_walks
        if s_new.moves == 0:
            break
        part = p_new
    assert walks > 0
    hubs = np.diff(g.row_ptr).max() > cfg.max_scan_neighbors
    assert hubs == (case == "hub-guard")


@pytest.mark.parametrize("cap", [512, 8, 3, 1])
def test_scalar_family_helpers_match_reference(cap):
    """The scalar flood fill gives the seed's family members in the seed's
    order, hubs under the guard included, and the scalar gains toward each
    destination and from home are the seed's receiver gain and sender loss,
    bit for bit."""
    k = 4
    g, arrays, part = _setup(7, provgen_like, CASES[0][2], k)
    fld = _jittered(extroversion_field(g, arrays, part, k), 0)
    cfg = SwapConfig(family_threshold=0.3, max_scan_neighbors=cap)
    rev = g.reverse_edge_index
    rel_mass_out = fld.edge_mass / np.maximum(fld.pr[g.src], 1e-30)
    rel_rev = np.where(rev >= 0, rel_mass_out[np.maximum(rev, 0)], -np.inf)
    join_e = (part[g.src] == part[g.dst]) & (rel_rev > cfg.family_threshold)
    sym_mass = fld.edge_mass.astype(np.float64) + np.where(
        rev >= 0, fld.edge_mass[np.maximum(rev, 0)].astype(np.float64), 0.0)
    moved = np.random.default_rng(1).random(g.n) < 0.1
    moved_b = bytearray(moved)
    rp, dl = g.row_ptr.tolist(), g.dst.tolist()
    deg = np.diff(g.row_ptr)
    n_multi = n_hub = 0
    for v in np.nonzero(~moved)[0][:400].tolist():
        fam = _family_scalar(v, rp, dl, join_e.tobytes(), moved_b,
                             rel_mass_out, cfg.family_max_size, cap)
        assert fam == swap_ref._family_of(g, v, part, moved, rel_mass_out,
                                          cfg)
        gains = _family_gains_scalar(fam, rp, dl, part, sym_mass, k)
        home = int(part[v])
        for dest in range(k):
            if dest != home:
                gain, loss = swap_ref._family_gain(g, fam, dest, part,
                                                   fld.edge_mass)
                assert (gains[dest], gains[home]) == (gain, loss)
        if len(fam) > 1:
            n_multi += 1
            n_hub += bool((deg[fam] > cap).any())
    assert n_multi > 0
    assert (n_hub > 0) == (cap < deg.max())


def test_reverse_edge_index_is_involution():
    g = musicbrainz_like(2000, seed=1)
    rev = g.reverse_edge_index
    assert rev.shape == (g.m,)
    assert (rev >= 0).all()  # symmetric graph: every edge has its reverse
    assert (g.src[rev] == g.dst).all()
    assert (g.dst[rev] == g.src).all()
    assert (rev[rev] == np.arange(g.m)).all()


def _one_device_sharded(g, arrays, part, k, **kw):
    """The ``vm_step`` kernel program on a mesh of one device."""
    return extroversion_field(g, arrays, part, k, backend="pallas_sharded",
                              _precomputed={"_mesh": make_smoke_mesh(1)},
                              **kw)


@pytest.mark.parametrize("dense", [True, False], ids=["dense", "two-phase"])
def test_sharded_field_on_one_device_mesh_matches_jnp(dense):
    g, arrays, part = _setup(9, provgen_like,
                             ["Entity.Entity.Entity", "Agent.Activity.Entity"],
                             4, n=800)
    f_jnp = extroversion_field(g, arrays, part, 4, dense_ext_to=dense,
                               backend="jnp")
    f_sh = _one_device_sharded(g, arrays, part, 4, dense_ext_to=dense)
    np.testing.assert_allclose(f_sh.alpha, f_jnp.alpha, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(f_sh.edge_mass, f_jnp.edge_mass,
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(f_sh.pr, f_jnp.pr, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(f_sh.extro_mass, f_jnp.extro_mass,
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(f_sh.extroversion, f_jnp.extroversion,
                               rtol=1e-3, atol=1e-6)
    if dense:
        np.testing.assert_allclose(f_sh.ext_to, f_jnp.ext_to,
                                   rtol=1e-4, atol=1e-6)
    else:
        assert f_sh.ext_to is None and f_jnp.ext_to is None
    assert f_sh.total_extroversion == pytest.approx(
        f_jnp.total_extroversion, rel=1e-4, abs=1e-6)


def test_sharded_field_on_one_device_mesh_depth_cap():
    g, arrays, part = _setup(2, provgen_like, ["Entity.Entity.Entity"], 3,
                             n=500)
    f_jnp = extroversion_field(g, arrays, part, 3, depth_cap=2, backend="jnp")
    f_sh = _one_device_sharded(g, arrays, part, 3, depth_cap=2)
    np.testing.assert_allclose(f_sh.edge_mass, f_jnp.edge_mass,
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(f_sh.pr, f_jnp.pr, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("where", ["extroversion_field", "TaperConfig"])
def test_unknown_field_backend_rejected(where):
    """Only ``jnp`` and ``pallas_sharded`` exist: any other backend name,
    ``pallas`` among them, fails where it is named — at the field call, or
    when the Taper is built, not at its first field evaluation."""
    removed = 'pallas'
    g, arrays, part = _setup(2, provgen_like, ["Entity.Entity"], 2, n=200)
    with pytest.raises(ValueError, match=f"unknown field backend {removed!r}"):
        if where == "extroversion_field":
            extroversion_field(g, arrays, part, 2, backend=removed)
        else:
            Taper(g, 2, TaperConfig(field_backend=removed))


def test_taper_field_lazy_reuse_on_unchanged_trie():
    """§4.2: unchanged trie probabilities + unchanged partition -> the field
    is reused, not recomputed."""
    g = provgen_like(400, seed=8)
    k = 2
    w = [(parse_rpq("Entity.Entity"), 1.0)]
    trie = TPSTry.from_workload(w)
    taper = Taper(g, k, TaperConfig(max_iterations=1, seed=0))
    part = hash_partition(g.n, k, seed=3)
    r1 = taper.invoke(part, trie)
    calls = {"n": 0}
    import repro.core.taper as taper_mod
    orig = taper_mod.extroversion_field

    def counting(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    taper_mod.extroversion_field = counting
    try:
        r2 = taper.invoke(part, trie)
    finally:
        taper_mod.extroversion_field = orig
    # first field evaluation of the repeat invocation hits the memo
    assert r2.objective[0] == r1.objective[0]
    assert calls["n"] < max(r2.iterations + 1, 1) + 1
