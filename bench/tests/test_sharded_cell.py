"""The sharded cell (``taper-paper-10m.invoke``) at n = 3,000 on four
forced host devices: a sound run is correct, and a run with the sharded
field broken underneath (the halo payload zeroed, or beta carried between
depth steps in bfloat16) is not.  The harness's look for a chip is
skipped (``run_cell`` directly).  This process has one CPU device, so one
child process with four runs every case; the breakages are patched in the
child, around the program, not switched on inside it."""
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH

N = 3000
CELL = "taper-paper-10m.invoke"

CHILD = """
import json, sys
sys.path.insert(0, %(bench)r)
sys.path.insert(0, %(src)r)
import numpy as np
import jax
import jax.numpy as jnp

import run as harness
from repro.core import visitor
from repro.graphs.sharded_packing import compute_shard_order
from repro.kernels.vm_step import kernel

bm = harness.load_benchmark()
cell, cfg, traffic = harness.cell_files(bm, %(cell)r)
SEED = 2 ** 33 + 11


def once():
    visitor._FIELD_CACHE.clear()     # the next field is traced anew
    out = harness.run_cell(cell["name"], cell, cfg, traffic, seed=SEED,
                           seconds=1.0, trace=False, peak=None, n=%(n)d)
    return {"correct": out["correct"], "checks": out["checks"]}


res = {"sound": once()}

lax = jax.lax
real = lax.ppermute, lax.psum
lax.ppermute = lambda x, axis_name, perm: jnp.zeros_like(x)
lax.psum = lambda x, axis_name: jnp.zeros_like(x)
res["halo_zeroed"] = once()
lax.ppermute, lax.psum = real

real_step = kernel.vm_step_packed
kernel.vm_step_packed = lambda *a, **kw: real_step(*a, **kw).astype(
    jnp.bfloat16).astype(jnp.float32)
res["beta_bf16"] = once()
kernel.vm_step_packed = real_step

ctx = harness.Ctx(cfg, traffic, SEED, 1.0, False, n=%(n)d)
drv = harness.load_module(harness.BENCH / "drivers" / "invoke_sharded.py",
                          "driver_invoke_sharded").Driver(ctx)
drv.setup()
token, order = drv.taper._pre["_shard_order"]
want = compute_shard_order(drv.taper.g, "partition", 4, part=drv.part0)
res["order"] = {"token": token, "equal": bool(np.array_equal(order, want)),
                "devices": drv.taper._pre["_halo_stats"]["n_devices"]}
print("RESULT " + json.dumps(res))
"""


@pytest.fixture(scope="module")
def runs():
    src = str(BENCH.parent / "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [src, os.environ.get("PYTHONPATH", "")]))
    code = CHILD % {"bench": str(BENCH), "src": src, "cell": CELL, "n": N}
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    line = [x for x in r.stdout.splitlines() if x.startswith("RESULT ")]
    assert line, r.stdout[-2000:]
    return json.loads(line[-1][len("RESULT "):])


def test_sound_sharded_run_is_correct(runs):
    assert runs["sound"]["correct"], runs["sound"]["checks"]


def test_zeroed_halo_payload_is_not_correct(runs):
    assert not runs["halo_zeroed"]["correct"], runs["halo_zeroed"]["checks"]


def test_bf16_beta_fails_the_field_limit(runs):
    """Carrying beta in bfloat16, the precision below the float32 the
    configuration states, is caught by ``field_rel_err`` alone."""
    cfg = json.loads(
        (BENCH / "configs" / "taper-paper-10m.json").read_text())
    checks = runs["beta_bf16"]["checks"]
    assert not runs["beta_bf16"]["correct"]
    assert checks["field_rel_err"]["limit"] == cfg["limits"]["field_rel_err"]
    assert checks["field_rel_err"]["value"] > checks["field_rel_err"]["limit"]


def test_shards_follow_the_start_partition(runs):
    """Set-up deals the shards along the start, not along the warm-up
    field's shifted copy of it."""
    assert runs["order"]["token"] == "partition:0"
    assert runs["order"]["equal"]
    assert runs["order"]["devices"] == 4
