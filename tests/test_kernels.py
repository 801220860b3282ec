"""Pallas kernel validation (interpret mode) against pure-jnp oracles.

Per assignment: for each kernel, sweep shapes/dtypes and assert_allclose
against the ref.py oracle (hypothesis-driven sweeps + fixed edge cases).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import HealthCheck, given, settings, strategies as st

from repro.kernels.embedding_bag.ops import embedding_bag_pallas
from repro.kernels.embedding_bag.ref import embedding_bag_reference
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_reference
from repro.kernels.segment_spmm.ops import pack_edges, pack_weights, segment_spmm
from repro.kernels.segment_spmm.ref import segment_spmm_reference
from repro.kernels.vm_step.ops import pack_vm_inputs, vm_step
from repro.kernels.vm_step.ref import build_transition, vm_step_reference

SET = settings(max_examples=10, deadline=None,
               suppress_health_check=list(HealthCheck))


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@given(
    b=st.integers(1, 3),
    sq=st.integers(1, 300),
    skv=st.integers(1, 300),
    h=st.sampled_from([1, 2, 4]),
    g=st.sampled_from([1, 2]),
    d=st.sampled_from([32, 64]),
    causal=st.booleans(),
    window=st.sampled_from([None, 17, 64]),
    dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
)
@SET
def test_flash_attention_sweep(b, sq, skv, h, g, d, causal, window, dtype):
    if causal and sq > skv:
        sq = skv  # decode-style causal assumes q suffix aligns; keep simple
    rng = np.random.default_rng(abs(hash((b, sq, skv, h, g, d))) % 2**31)
    kv = h
    H = h * g
    q = jnp.asarray(rng.normal(size=(b, sq, H, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, skv, kv, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, skv, kv, d)), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=64, block_k=64)
    kf, vf = jnp.repeat(k, g, 2), jnp.repeat(v, g, 2)
    ref = attention_reference(q, kf, vf, causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **_tol(dtype))


def test_flash_attention_long_and_blocks():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 1024, 2, 64)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 1024, 2, 64)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 1024, 2, 64)).astype(np.float32))
    ref = attention_reference(q, k, v, causal=True)
    for bq, bk in [(128, 128), (256, 64), (64, 256)]:
        out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# segment spmm
# ---------------------------------------------------------------------------


@given(
    n=st.integers(5, 400),
    e=st.integers(1, 1500),
    f=st.sampled_from([8, 32, 64]),
    block_n=st.sampled_from([32, 128]),
    block_e=st.sampled_from([64, 256]),
    seed=st.integers(0, 2**16),
)
@SET
def test_segment_spmm_sweep(n, e, f, block_n, block_e, seed):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, e).astype(np.int32)
    dst = rng.integers(0, n, e).astype(np.int32)
    w = rng.normal(size=e).astype(np.float32)
    x = jnp.asarray(rng.normal(size=(n, f)).astype(np.float32))

    packed = pack_edges(src, dst, n, block_n, block_e)
    w_packed = pack_weights(packed, w)
    out = segment_spmm(x, packed, w_packed, n)
    ref = segment_spmm_reference(x, jnp.asarray(src), jnp.asarray(dst),
                                 jnp.asarray(w), n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def _pack_edges_loop(edge_src, edge_dst, n, block_n, block_e):
    """Per-destination-block loop transcription of ``pack_edges``: the
    reference its vectorised form must reproduce exactly."""
    order = np.argsort(edge_dst, kind="stable")
    src_s, dst_s = edge_src[order], edge_dst[order]
    blk = dst_s // block_n
    src, dloc, mask, meta = [], [], [], []
    for b in range(-(-n // block_n)):
        sel = blk == b
        cnt = int(sel.sum())
        n_eb = max(1, -(-cnt // block_e))
        pad = n_eb * block_e - cnt
        src.append(np.concatenate([src_s[sel], np.zeros(pad, src_s.dtype)]))
        dloc.append(np.concatenate(
            [dst_s[sel] - b * block_n, np.zeros(pad, dst_s.dtype)]))
        mask.append(np.concatenate([np.ones(cnt, bool), np.zeros(pad, bool)]))
        meta += [(b, int(j == 0)) for j in range(n_eb)]
    return (np.concatenate(src).astype(np.int32),
            np.concatenate(dloc).astype(np.int32),
            np.asarray(meta, np.int32), np.concatenate(mask), order)


@pytest.mark.parametrize("n,e,block_n,block_e", [
    (1, 0, 8, 8), (97, 5, 32, 64), (500, 2999, 128, 256), (300, 1024, 8, 8),
])
def test_pack_edges_matches_loop_reference(n, e, block_n, block_e):
    rng = np.random.default_rng(n + e)
    src = rng.integers(0, n, e)
    dst = rng.integers(0, n, e)
    packed = pack_edges(src, dst, n, block_n, block_e)
    ref = _pack_edges_loop(src, dst, n, block_n, block_e)
    got = (packed.src, packed.dst_local, packed.meta, packed.pad_mask,
           packed.order)
    for a, b in zip(got, ref):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(a, b)
    assert packed.n_blocks_out == -(-n // block_n)


def test_segment_spmm_fallback_matches():
    rng = np.random.default_rng(1)
    n, e, f = 100, 400, 16
    src = rng.integers(0, n, e).astype(np.int32)
    dst = rng.integers(0, n, e).astype(np.int32)
    w = rng.normal(size=e).astype(np.float32)
    x = jnp.asarray(rng.normal(size=(n, f)).astype(np.float32))
    packed = pack_edges(src, dst, n, 32, 64)
    wp = pack_weights(packed, w)
    out_k = segment_spmm(x, packed, wp, n, use_pallas=True)
    out_f = segment_spmm(x, packed, wp, n, use_pallas=False)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_f),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# vm step (TAPER DP)
# ---------------------------------------------------------------------------


def _random_trie(rng, n_labels, depth=3, branching=2):
    from repro.core.tpstry import synthetic_trie

    return synthetic_trie(n_labels, depth, branching,
                          n_first=min(3, n_labels), seed=int(rng.integers(1e6)))


@given(
    n=st.integers(10, 300),
    e=st.integers(5, 1200),
    n_labels=st.sampled_from([3, 6, 12]),
    seed=st.integers(0, 2**16),
)
@SET
def test_vm_step_sweep(n, e, n_labels, seed):
    rng = np.random.default_rng(seed)
    trie = _random_trie(rng, n_labels)
    N = trie.n_nodes
    src = rng.integers(0, n, e).astype(np.int32)
    dst = rng.integers(0, n, e).astype(np.int32)
    labels = rng.integers(0, n_labels, n).astype(np.int32)
    cnt = rng.integers(1, 5, (n, n_labels)).astype(np.int32)
    alpha = jnp.asarray(rng.random((n, N)).astype(np.float32))
    T = jnp.asarray(build_transition(trie.parent, trie.label, trie.cond_p,
                                     n_labels))

    packed, dst_label, inv_cnt = pack_vm_inputs(src, dst, labels, cnt, n,
                                                block_n=64, block_e=128)
    out = vm_step(alpha, T, packed, dst_label, inv_cnt, n)
    inv_ref = 1.0 / np.maximum(cnt[src, labels[dst]], 1.0)
    ref = vm_step_reference(alpha, T, jnp.asarray(src), jnp.asarray(dst),
                            jnp.asarray(inv_ref.astype(np.float32)),
                            jnp.asarray(labels[dst]), n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("n,e,n_labels,block_n,block_e", [
    (300, 1000, 3, 64, 256),    # 1000 edges: no multiple of the edge block
    (257, 777, 12, 128, 256),   # L = 12: twelve masked label steps
    (90, 3, 12, 32, 128),       # most destination blocks are all padding
])
def test_vm_step_gather_free_parity(n, e, n_labels, block_n, block_e):
    """The gather-free kernel (sources gathered in XLA, one masked 2-D dot
    per label, feature-major blocks) against the jnp oracle."""
    rng = np.random.default_rng(n * 7919 + e)
    trie = _random_trie(rng, n_labels)
    src = rng.integers(0, n, e).astype(np.int32)
    dst = rng.integers(0, n, e).astype(np.int32)
    labels = rng.integers(0, n_labels, n).astype(np.int32)
    cnt = rng.integers(1, 5, (n, n_labels)).astype(np.int32)
    alpha = jnp.asarray(rng.random((n, trie.n_nodes)).astype(np.float32))
    T = jnp.asarray(build_transition(trie.parent, trie.label, trie.cond_p,
                                     n_labels))
    packed, dst_label, inv_cnt = pack_vm_inputs(
        src, dst, labels, cnt, n, block_n=block_n, block_e=block_e)
    assert packed.src.shape[0] % block_e == 0 and e % block_e != 0
    out = vm_step(alpha, T, packed, dst_label, inv_cnt, n)
    inv_ref = 1.0 / np.maximum(cnt[src, labels[dst]], 1.0)
    ref = vm_step_reference(alpha, T, jnp.asarray(src), jnp.asarray(dst),
                            jnp.asarray(inv_ref.astype(np.float32)),
                            jnp.asarray(labels[dst]), n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


_SHARDED_PARITY = """
import numpy as np
from repro.core.rpq import parse_rpq
from repro.core.tpstry import TPSTry
from repro.core.visitor import extroversion_field
from repro.graphs.generators import power_law_labelled
from repro.graphs.partition import hash_partition

g = power_law_labelled(700, n_labels=12, seed=3)
arrays = TPSTry.from_workload(
    [(parse_rpq("L0.L1.(L2|L3).L1"), 0.6), (parse_rpq("L4.L5.L0"), 0.4)]
).compile(g.label_names)
part = hash_partition(g.n, 4, seed=0)
ref = extroversion_field(g, arrays, part, 4, backend="jnp")
for source in ("stripe", "partition"):
    pre = {}
    sh = extroversion_field(g, arrays, part, 4, _precomputed=pre,
                            backend="pallas_sharded",
                            shard_map_source=source, halo_exchange="sliced")
    assert pre["_halo_stats"]["n_devices"] == 4, pre["_halo_stats"]
    for f in ("alpha", "edge_mass", "extroversion", "ext_to"):
        np.testing.assert_allclose(getattr(sh, f), getattr(ref, f),
                                   rtol=1e-4, atol=2e-6, err_msg=f)
print("sharded parity ok")
"""


def test_vm_step_sharded_parity_on_forced_host_devices():
    """The kernel under ``shard_map`` on four forced host devices (this
    process has one CPU device, so a child process gets four), for both
    shard maps, against the single-device jnp field."""
    import os
    import subprocess
    import sys

    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [src, os.environ.get("PYTHONPATH", "")]))
    r = subprocess.run([sys.executable, "-c", _SHARDED_PARITY], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "sharded parity ok" in r.stdout


def test_vm_step_matches_visitor_dp(paper_graph, paper_trie, paper_partition):
    """The kernel advances alpha exactly like the visitor-field DP: applying
    it to the paper graph's depth-1 priors must reproduce the depth-2 alpha
    states of the §5.4 worked example (restricted to local edges)."""
    from repro.core.visitor import extroversion_field

    g = paper_graph
    arrays = paper_trie.compile(g.label_names)
    fld = extroversion_field(g, arrays, paper_partition, k=2)

    # build alpha0 with only depth-1 states
    N = arrays.n_nodes
    alpha0 = np.zeros((g.n, N), np.float32)
    for i in range(N):
        if arrays.depth[i] == 1:
            alpha0[:, i] = np.asarray(fld.alpha[:, i])
    # only local edges advance the DP
    local = paper_partition[g.src] == paper_partition[g.dst]
    src, dst = g.src[local], g.dst[local]
    cnt = g.neighbor_label_counts()
    T = jnp.asarray(build_transition(arrays.parent, arrays.label,
                                     arrays.cond_p, arrays.n_labels))
    packed, dst_label, inv_cnt = pack_vm_inputs(src, dst, g.labels, cnt, g.n,
                                                block_n=8, block_e=8)
    out = np.asarray(vm_step(jnp.asarray(alpha0), T, packed, dst_label,
                             inv_cnt, g.n))
    for i in range(N):
        if arrays.depth[i] == 2:
            np.testing.assert_allclose(out[:, i], np.asarray(fld.alpha[:, i]),
                                       rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# embedding bag
# ---------------------------------------------------------------------------


@given(
    v=st.integers(10, 3000),
    d=st.sampled_from([8, 32, 64]),
    b=st.integers(1, 300),
    h=st.sampled_from([1, 2, 8]),
    combiner=st.sampled_from(["sum", "mean"]),
    seed=st.integers(0, 2**16),
)
@SET
def test_embedding_bag_sweep(v, d, b, h, combiner, seed):
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.normal(size=(v, d)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, v, (b, h)).astype(np.int32))
    out = embedding_bag_pallas(table, ids, combiner=combiner,
                               block_b=64, block_v=256)
    ref = embedding_bag_reference(table, ids, combiner)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_embedding_bag_repeated_ids():
    # a bag hitting the same row multiple times must count it multiple times
    table = jnp.asarray(np.eye(8, 4, dtype=np.float32))
    ids = jnp.asarray([[2, 2, 2, 0]], dtype=jnp.int32)
    out = embedding_bag_pallas(table, ids, block_b=8, block_v=8)
    ref = embedding_bag_reference(table, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref))
