"""The device path compiled for a described TPU v5e (no chip attached).

The TPU compiler refuses here what interpret mode cannot see: an in-kernel
gather, a block that does not tile, a scalar table beyond SMEM, a program
beyond device memory.  Shapes are those of ProvGen at the paper's size:
n = 1,000,000 vertices, m ~ 5.5M directed edges, 3 labels, the 23-node
PQ1-4 trie, k = 8.  Nothing runs, so these tests say nothing of results
or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

N_V = 1_000_000
M_E = 5_520_000              # provgen_like(1_000_000, 6.0, seed=11) has ~5.52M
K = 8
BLOCK_N, BLOCK_E = 128, 256
N_BLOCKS = -(-N_V // BLOCK_N)
EDGE_BLOCKS = 23_500         # pack_edges of that graph needs ~23.4K blocks
HBM_BYTES = 16 * 10 ** 9     # one v5e chip
SCAN_LEVELS = 10             # the largest in-degree of a ProvGen 1M graph, 997


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Mosaic-compile the kernels although JAX's backend here is the CPU,
    where they would otherwise run interpreted."""
    from repro.kernels.vm_step import kernel

    monkeypatch.setattr(kernel, "default_interpret", lambda: False)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def pq_trie():
    """The PQ1-4 workload trie at its paper frequencies
    (``benchmarks/common.py``)."""
    from repro.core.rpq import parse_rpq
    from repro.core.tpstry import TPSTry
    from repro.graphs.generators import PROV_LABELS

    workload = [
        (parse_rpq("Entity.(Entity)*.Entity"), 0.4),
        (parse_rpq("Agent.Activity.Entity.Entity.Activity.Agent"), 0.2),
        (parse_rpq("(Entity)*.Activity.Entity"), 0.2),
        (parse_rpq("Entity.Activity.(Agent)*"), 0.2),
    ]
    return TPSTry.from_workload(workload).compile(PROV_LABELS)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_fused_jnp_field_compiles_at_provgen_1m(one_chip, pq_trie):
    """The default field path without the dense ``(n, k)`` ext_to matrix,
    with the destination order its depth scans sum over, compiles at
    ProvGen's 1M size and fits a quarter of one chip's memory."""
    from repro.core.visitor import _build_field_fn

    t = pq_trie
    fn = _build_field_fn(None, t, K, t.max_depth, dense_ext_to=False)
    L, N = t.n_labels, t.n_nodes
    s = lambda shape, dt: _spec(shape, dt, one_chip)
    edges, ptr = s((M_E,), jnp.int32), s((N_V + 1,), jnp.int32)
    compiled = fn.lower(
        edges, edges, s((N_V,), jnp.int32),
        s((N_V, L), jnp.int32), s((L,), jnp.int32), s((N_V,), jnp.int32),
        s((N,), jnp.float32), s((N,), jnp.float32),
        edges, edges, edges, ptr, ptr,       # the destination order
        n=N_V, m=M_E, levels=SCAN_LEVELS).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES // 4, mem


def _compile_vm_step(one_chip, pq_trie, edge_blocks):
    from repro.kernels.vm_step.kernel import vm_step_packed

    L, N = pq_trie.n_labels, pq_trie.n_nodes
    E = edge_blocks * BLOCK_E
    s = lambda shape, dt: _spec(shape, dt, one_chip)

    def step(a, T, dst_local, dst_label, inv_cnt, meta):
        return vm_step_packed(a, T, dst_local, dst_label, inv_cnt, meta,
                              N_BLOCKS, BLOCK_N, BLOCK_E)

    return jax.jit(step).lower(
        s((N, E), jnp.float32), s((L, N, N), jnp.float32),
        s((E,), jnp.int32), s((E,), jnp.int32), s((E,), jnp.float32),
        s((2, edge_blocks), jnp.int32)).compile()


def test_vm_step_kernel_compiles_at_provgen_1m(one_chip, pq_trie,
                                              compiled_kernels):
    compiled = _compile_vm_step(one_chip, pq_trie, EDGE_BLOCKS)
    assert "tpu_custom_call" in compiled.as_text()
    # every operand is used in place: no padded relayout copy of the
    # (N, E) source columns or the (1, E) edge channels
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


def test_vm_step_block_table_smem_limit(one_chip, pq_trie, compiled_kernels):
    from repro.kernels.vm_step.kernel import MAX_EDGE_BLOCKS

    _compile_vm_step(one_chip, pq_trie, MAX_EDGE_BLOCKS)
    # a block table of exactly 1 MiB leaves no SMEM for anything else
    with pytest.raises(Exception, match="(?i)smem"):
        _compile_vm_step(one_chip, pq_trie, (1 << 20) // 8)


def test_sharded_field_body_compiles_on_four_chips(topo, pq_trie,
                                                  compiled_kernels):
    from repro.core.visitor import _build_sharded_fn

    S = 4
    mesh = Mesh(np.asarray(topo.devices[:S]).reshape(1, S),
                ("data", "model"))
    t = pq_trie
    L, N = t.n_labels, t.n_nodes
    bps = -(-N_BLOCKS // S)
    n_local_pad = bps * BLOCK_N
    eb_cap = EDGE_BLOCKS // S + bps // 8 + 2
    e_pad = eb_cap * BLOCK_E
    hot_pad = 1024
    round_cap = (0, 40_000, 40_000, 40_000)
    fn = _build_sharded_fn(mesh, t, t.max_depth, bps, BLOCK_N, BLOCK_E,
                           n_local_pad, exchange="sliced", n_shards=S,
                           round_cap=round_cap, n=N_V, m=M_E, k=K,
                           dense_ext_to=False, identity=False)
    sh = NamedSharding(mesh, P("model"))
    rep = NamedSharding(mesh, P())
    i32, f32 = jnp.int32, jnp.float32
    args = (
        _spec((S, eb_cap, 2), i32, sh),          # meta
        _spec((S, e_pad), i32, sh),              # src_map (sliced)
        _spec((S, e_pad), i32, sh),              # dst_local
        _spec((S, e_pad), i32, sh),              # dst_label
        _spec((S, e_pad), f32, sh),              # inv_cnt
        _spec((S, e_pad), i32, sh),              # src_global
        _spec((S, e_pad), i32, sh),              # dst_global
        _spec((S, n_local_pad), i32, sh),        # vlabels
        _spec((S, e_pad), i32, sh),              # slot_raw
        _spec((S, hot_pad), i32, sh),            # hot_local_idx
        _spec((S, hot_pad), f32, sh),            # hot_owned
        _spec((S, S, max(round_cap)), i32, sh),  # send_local
        _spec((N_V,), i32, rep),                 # pos (vertex -> position)
        _spec((N_V,), i32, rep),                 # part
        _spec((N,), f32, rep),                   # p
        _spec((L,), i32, rep),                   # lab_vcount
        _spec((L, N, N), f32, rep),              # T
        _spec((L, N), f32, rep),                 # Tsum
    )
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "collective-permute" in text and "all-reduce" in text
    # one program for the whole field: loop, reorder, scatter, aggregates
    assert "sharded_field_fn" in text.splitlines()[0]
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < HBM_BYTES // 4, mem
