"""Fault injection and graceful degradation: the serving loop must keep
answering queries under every injected fault class — invocation crashes
and stalls (watchdog abort-and-retry with backoff), shard-upload failures,
poisoned coalesced ingest groups — degrading the field backend down the
``pallas_sharded -> pallas -> jnp`` ladder and probing back up."""
import time

import numpy as np
import pytest

from repro.core.online import OnlinePolicy
from repro.core.rpq import parse_rpq
from repro.core.taper import TaperConfig
from repro.graphs.generators import musicbrainz_like
from repro.graphs.graph import MutationBatch
from repro.serve import ServeLoopConfig, ServingLoop
from repro.serve.faults import (
    SITE_INGEST_GROUP,
    SITE_INVOCATION,
    SITE_SHARD_UPLOAD,
    FaultInjector,
    FaultSpec,
    InjectedFault,
)

MQ1 = parse_rpq("Area.Artist.(Artist|Label).Area")


def _eager_policy():
    """Invoke on every tick (cadence 1), decisions from durable state."""
    return OnlinePolicy(bootstrap_after_ticks=0, cadence=1, min_interval=0,
                        dirty_fraction=2.0, drift_l1=9e9, ipt_regression=9e9)


def _quiet_policy():
    """Never invoke: isolates ingest/upload paths from the swap engine."""
    return OnlinePolicy(bootstrap_after_ticks=None, cadence=10 ** 9,
                        min_interval=0, dirty_fraction=2.0, drift_l1=9e9,
                        ipt_regression=9e9)


def _topology_policy():
    """Invoke only on topology dirt (any dirty vertex trips it)."""
    return OnlinePolicy(bootstrap_after_ticks=None, cadence=10 ** 9,
                        min_interval=0, dirty_fraction=1e-9, drift_l1=9e9,
                        ipt_regression=9e9)


# ---------------------------------------------------------------------------
# the injector itself
# ---------------------------------------------------------------------------


def test_fault_injector_arm_fire_exhaust_disarm():
    fi = FaultInjector()
    fi.fire("invocation")                  # unarmed site: no-op
    fi.arm("invocation", times=2)
    with pytest.raises(InjectedFault):
        fi.fire("invocation")
    with pytest.raises(InjectedFault):
        fi.fire("invocation")
    fi.fire("invocation")                  # exhausted after ``times`` shots
    assert fi.fired_total() == 2
    fi.arm("shard_upload", times=-1)       # <=0: fires forever
    for _ in range(3):
        with pytest.raises(InjectedFault):
            fi.fire("shard_upload")
    fi.disarm("shard_upload")
    fi.fire("shard_upload")
    assert fi.fired_total() == 5
    with pytest.raises(ValueError):
        FaultSpec(mode="explode")


def test_fault_injector_rejects_unknown_site():
    """A typo'd site must fail at arm time, not pass vacuously by never
    firing (PR 10 satellite)."""
    fi = FaultInjector()
    with pytest.raises(ValueError, match="unknown fault site 'invocatoin'"):
        fi.arm("invocatoin")
    with pytest.raises(ValueError, match="valid sites: "):
        fi.arm("not_a_site:replica-1")
    # qualified arms of known sites still work
    fi.arm("replica_serve:replica-1")
    assert fi.armed("replica_serve:replica-1")


def test_fault_injector_stall_mode_sleeps_not_raises():
    fi = FaultInjector()
    fi.arm("invocation", mode="stall", delay_s=0.05)
    t0 = time.perf_counter()
    fi.fire("invocation")                  # stall: delay, no exception
    assert time.perf_counter() - t0 >= 0.04
    assert fi.fired_total() == 1


# ---------------------------------------------------------------------------
# degradation ladder
# ---------------------------------------------------------------------------


def test_backend_fallback_and_probe_recovery():
    pytest.importorskip("jax")
    g = musicbrainz_like(300, seed=21)
    fi = FaultInjector()
    loop = ServingLoop(
        g, 4, taper_config=TaperConfig(max_iterations=2,
                                       field_backend="pallas_sharded"),
        policy=_eager_policy(),
        config=ServeLoopConfig(
            micro_batch=4, overlap_invocations=False, faults=fi,
            invocation_retry_backoff_s=0.0, backend_fallback_after=2,
            backend_probe_after=1))
    fi.arm(SITE_INVOCATION, times=4)
    while fi.fired_total() < 4:
        loop.submit(MQ1)
        try:
            loop.pump()
        except InjectedFault:
            # the inline drive re-raises the invocation fault, but only
            # after the micro-batch was served — queries never stall
            pass
    served_during_faults = loop.metrics.completed
    assert served_during_faults >= 4
    # 4 consecutive failures at threshold 2: pallas_sharded -> jnp, then
    # pinned at the bottom rung (no further fallback to record)
    s = loop.stats()
    assert s["field_backend"] == "jnp"
    assert s["backend_fallbacks"] == 1
    assert s["degraded"] == 1 and s["healthy"] == 0
    assert loop.metrics.invocation_failures == 4
    # healthy commits at probe_after=1 walk back up: jnp -> pallas_sharded
    while loop.stats()["backend_recoveries"] < 1:
        loop.submit(MQ1)
        loop.pump()
    s = loop.stats()
    assert s["field_backend"] == "pallas_sharded"
    assert s["degraded"] == 0 and s["healthy"] == 1
    assert s["completed"] >= served_during_faults + 1


def test_jnp_is_the_bottom_rung():
    """A store that starts on the one-device program has nowhere to fall:
    repeated invocation failures record no fallback and keep ``jnp``."""
    pytest.importorskip("jax")
    g = musicbrainz_like(300, seed=21)
    fi = FaultInjector()
    loop = ServingLoop(
        g, 4, taper_config=TaperConfig(max_iterations=2, field_backend="jnp"),
        policy=_eager_policy(),
        config=ServeLoopConfig(
            micro_batch=4, overlap_invocations=False, faults=fi,
            invocation_retry_backoff_s=0.0, backend_fallback_after=2))
    fi.arm(SITE_INVOCATION, times=4)
    while fi.fired_total() < 4:
        loop.submit(MQ1)
        try:
            loop.pump()
        except InjectedFault:
            pass
    s = loop.stats()
    assert loop.metrics.invocation_failures == 4
    assert s["backend_fallbacks"] == 0
    assert s["field_backend"] == "jnp"
    assert s["degraded"] == 0


def test_invocation_failure_sets_retry_backoff():
    g = musicbrainz_like(300, seed=22)
    fi = FaultInjector()
    loop = ServingLoop(
        g, 4, taper_config=TaperConfig(max_iterations=2),
        policy=_eager_policy(),
        config=ServeLoopConfig(
            micro_batch=4, overlap_invocations=False, faults=fi,
            invocation_retry_backoff_s=30.0, backend_fallback_after=99))
    fi.arm(SITE_INVOCATION, times=1)
    loop.submit(MQ1)
    with pytest.raises(InjectedFault):
        loop.pump()
    assert loop._backoff_until > time.monotonic() + 10
    inv = loop.ot.invocations
    loop.submit(MQ1)
    assert loop.pump() == 1                # still serving inside the backoff
    assert loop.ot.invocations == inv      # ...but no retry until it expires
    loop._backoff_until = 0.0
    loop.submit(MQ1)
    loop.pump()
    assert loop.ot.invocations == inv + 1  # retried once the backoff passed


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------


def test_watchdog_aborts_stalled_invocation_and_gates_ingest():
    g = musicbrainz_like(300, seed=23)
    fi = FaultInjector()
    loop = ServingLoop(
        g, 4, taper_config=TaperConfig(max_iterations=2),
        policy=_eager_policy(),
        config=ServeLoopConfig(
            micro_batch=4, overlap_invocations=True, faults=fi,
            invocation_timeout_s=0.05, invocation_retry_backoff_s=0.0))
    fi.arm(SITE_INVOCATION, mode="stall", delay_s=0.6)
    loop.submit(MQ1)
    loop.pump()                            # spawns the stalled run
    assert loop.invocation_in_flight
    time.sleep(0.1)                        # blow the 50ms watchdog budget
    loop.pump()                            # watchdog: abort + abandon
    s = loop.stats()
    assert s["watchdog_aborts"] == 1
    assert "TimeoutError" in s["invocation_error"]
    assert s["healthy"] == 0
    assert not loop.invocation_in_flight
    assert loop._zombies_active()
    # the zombie still reads the graph: ingest (and new invocations) wait,
    # but queries keep being answered on the old partition
    v0, n0 = g.version, g.n
    assert loop.submit_mutations(MutationBatch(
        add_vertex_labels=[0], add_edges=[(0, n0)])) is True
    loop.submit(MQ1)
    assert loop.pump() == 1
    assert g.version == v0                 # mutation deferred, not lost
    for _ in range(100):                   # zombie exits at its abort check
        if not loop._zombies_active():
            break
        time.sleep(0.02)
    assert not loop._zombies_active()
    loop.pump()                            # deferred ingest now applies
    assert g.version == v0 + 1
    # drive one clean invocation so the abort was a blip, not an outage
    inv = loop.ot.invocations
    while loop.ot.invocations == inv:
        loop.submit(MQ1)
        loop.pump()
        loop._finish_inflight()
    assert loop.stats()["invocation_error"] == ""
    loop.stop()


def test_failed_invocation_leaves_dirty_bits_for_retry():
    """Satellite: an invocation that dies mid-run must not consume the
    dirty bits that triggered it — the next (clean) run retries them."""
    g = musicbrainz_like(300, seed=24)
    fi = FaultInjector()
    loop = ServingLoop(
        g, 4, taper_config=TaperConfig(max_iterations=2),
        policy=_topology_policy(),
        config=ServeLoopConfig(
            micro_batch=4, overlap_invocations=True, faults=fi,
            invocation_retry_backoff_s=0.0))
    # seed some workload so begin_invocation has something to fit
    loop.submit(MQ1)
    loop.pump()
    assert loop.submit_mutations(MutationBatch(
        add_vertex_labels=[0], add_edges=[(0, g.n)])) is True
    fi.arm(SITE_INVOCATION, times=1)
    loop.submit(MQ1)
    loop.pump()                            # applies ingest, spawns the run
    dirty_before = int(loop.ot._dirty.sum())
    assert dirty_before > 0
    assert loop._invocation_done.wait(5.0)
    loop.pump()                            # reaps the failed run
    s = loop.stats()
    assert "InjectedFault" in s["invocation_error"]
    assert s["healthy"] == 0
    assert int(loop.ot._dirty.sum()) == dirty_before   # unconsumed: retry
    inv = loop.ot.invocations
    while loop.ot.invocations == inv:      # clean retry consumes them
        loop.submit(MQ1)
        loop.pump()
        loop._finish_inflight()
    assert int(loop.ot._dirty.sum()) == 0
    assert loop.stats()["invocation_error"] == ""
    loop.stop()


# ---------------------------------------------------------------------------
# poisoned ingest group
# ---------------------------------------------------------------------------


def test_poisoned_ingest_group_falls_back_to_member_batches(tmp_path):
    g = musicbrainz_like(300, seed=25)
    fi = FaultInjector()
    loop = ServingLoop(
        g, 4, taper_config=TaperConfig(max_iterations=2),
        policy=_quiet_policy(),
        config=ServeLoopConfig(micro_batch=4, overlap_invocations=False,
                               faults=fi, snapshot_dir=str(tmp_path)))
    loop.snapshot(sync=True)
    v0, n0 = g.version, g.n
    fi.arm(SITE_INGEST_GROUP, times=1)
    for i in range(3):
        assert loop.submit_mutations(MutationBatch(
            add_vertex_labels=[i], add_edges=[(i, n0 + i)])) is True
    loop.pump()
    # the poisoned merged fold fell back to per-member application: every
    # batch landed (3 version bumps instead of 1), none were dropped
    assert g.version == v0 + 3
    assert loop.ingest.failed == 0
    assert fi.fired_total() == 1
    assert loop.stats()["failed_mutations"] == 0
    # recovery parity across the poisoned group: the outcome record makes
    # replay reproduce the per-member bumps (the fault is not re-raised)
    restored = ServingLoop.restore(
        tmp_path, taper_config=TaperConfig(max_iterations=2),
        policy=_quiet_policy(),
        config=ServeLoopConfig(micro_batch=4, overlap_invocations=False))
    assert restored.restore_result.replayed == 3
    assert restored.g.version == g.version
    assert restored.g.n == g.n
    assert np.array_equal(restored.g.src, g.src)
    assert np.array_equal(restored.ot._dirty, loop.ot._dirty)
    log_live = g.mutation_log
    log_back = restored.g.mutation_log
    assert [r.version for r in log_back] == [r.version for r in log_live]
    loop.stop()


# ---------------------------------------------------------------------------
# shard-upload failure
# ---------------------------------------------------------------------------


def test_shard_upload_fault_survivable_then_degrades():
    pytest.importorskip("jax")
    g = musicbrainz_like(300, seed=26)
    fi = FaultInjector()
    loop = ServingLoop(
        g, 4, taper_config=TaperConfig(max_iterations=2,
                                       field_backend="pallas_sharded"),
        policy=_quiet_policy(),
        config=ServeLoopConfig(
            micro_batch=4, overlap_invocations=False, faults=fi,
            invocation_retry_backoff_s=0.0, backend_fallback_after=2))
    fi.arm(SITE_SHARD_UPLOAD, times=1)
    v0, n0 = g.version, g.n
    assert loop.submit_mutations(MutationBatch(
        add_vertex_labels=[0], add_edges=[(1, n0)])) is True
    loop.pump()
    s = loop.stats()
    # the upload died but the mutation applied and serving continues on the
    # previous device buffers — survivable, one failure below the threshold
    assert g.version == v0 + 1
    assert s["upload_failures"] == 1
    assert s["degraded"] == 0
    loop.submit(MQ1)
    assert loop.pump() == 1
    # a second consecutive upload failure crosses the ladder threshold
    fi.arm(SITE_SHARD_UPLOAD, times=1)
    assert loop.submit_mutations(MutationBatch(
        add_vertex_labels=[0], add_edges=[(2, n0 + 1)])) is True
    loop.pump()
    s = loop.stats()
    assert s["upload_failures"] == 2
    assert s["backend_fallbacks"] == 1
    assert s["field_backend"] == "jnp" and s["degraded"] == 1
    loop.submit(MQ1)
    assert loop.pump() == 1                # still answering queries
    loop.stop()
