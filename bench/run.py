#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up in ``BENCHMARK.json``; its configuration file, its
traffic mix (``bench/traffic/<mix>.json``, which names a driver under
``bench/drivers/``) and its per-layer metric readers
(``bench/metrics/<metric>.py``) are all found by name, so a new cell,
mix or metric is new files and entries, not edits.

The run: set-up (graph from the seed, program state, compiles, warm-up),
then the measured window of ``--seconds``, then the comparison with the
plain reference.  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` records a profiler trace of the window and reports the
per-layer metrics.  The last line of standard output is one JSON object;
the numbers compared for ``correct`` are the last lines of standard error
and the last key of that object.  Without a TPU, or with fewer chips than
the cell asks for, the run exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from contextlib import nullcontext  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_trace"
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from common import CompileClock, Run, log  # noqa: E402


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark() -> Dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell_files(bm: Dict, name: str):
    """(cell entry, configuration, traffic mix) of a cell by name."""
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bm["configs"]}[cell["config"]]
    cfg = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    return cell, cfg, traffic


def applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class Ctx:
    """What a driver sees: the cell's files, the run's arguments, and an
    annotation factory that writes into the profiler trace when tracing."""

    def __init__(self, cfg, traffic, seed, seconds, trace, n=None, rate=None):
        self.cfg, self.traffic = cfg, traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.n, self.rate = n, rate

    def annotate(self, name: str):
        if not self.trace:
            return nullcontext()
        from jax.profiler import TraceAnnotation

        return TraceAnnotation(name)


class View:
    """What a per-layer metric reader sees (``read(view)``)."""

    def __init__(self, run: Run, trace: Optional[Dict], peak: Dict):
        self.run, self.trace, self.peak = run, trace, peak
        self.w0, self.w1 = run.notes["window_monotonic"]

    def spans(self, name: str) -> List[Dict]:
        """Program spans of that name that lie inside the window."""
        return [s for s in self.run.spans
                if s["name"] == name and s.get("t1") is not None
                and s["t0"] >= self.w0 and s["t1"] <= self.w1]


def run_cell(name: str, cell: Dict, cfg: Dict, traffic: Dict, seed: int,
             seconds: float, trace: bool, peak: Optional[Dict],
             n: Optional[int] = None) -> Dict:
    """Set-up, window and check of one cell; returns the result object.
    ``n`` overrides the configured size (tests only)."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = CompileClock(jax)
    ctx = Ctx(cfg, traffic, seed, seconds, trace, n=n)
    driver = load_module(BENCH / "drivers" / f"{traffic['driver']}.py",
                         f"driver_{traffic['driver']}").Driver(ctx)
    driver.setup()
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.3f} s: backend compile {clock.seconds:.3f} s in "
        f"{clock.compiles} compiles, persistent-cache hits {clock.hits}")
    c0 = clock.compiles
    anchor = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        anchor = time.monotonic()
    with ctx.annotate("bench.window"):
        driver.window()
    if trace:
        jax.profiler.stop_trace()
    in_window = clock.compiles - c0
    if in_window:
        log(f"WARNING: {in_window} compiles inside the window")
    devs = jax.devices()[:cell["chips"]]
    stats = [d.memory_stats() or {} for d in devs]
    mem_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    run: Run = driver.finish()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem_peak}
    bm = load_benchmark()
    out = {"correct": all(c.ok for c in run.checks),
           "attempted": run.attempted, "failed": run.failed}
    if not trace:
        vals = dict(run.end_to_end, setup_s=setup_s)
        out["metrics"] = {
            m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
            for m in bm["end_to_end"] if applies(m, name)}
    else:
        from field_cost import field_cost, least_time
        import trace_reduce as tr

        events = tr.load_events(str(TRACE_DIR))
        win = tr.host_span(events, "bench.window")
        b = tr.busy(events, win)
        host = [(ev, t, t + d) for p, _, ev, t, d in events
                if p == tr.HOST_PLANE and ev.startswith("bench.")
                and ev != "bench.window"]
        host += tr.to_profiler_clock(run.spans, anchor, win[0])
        red = {"busy": b, "field_module_s": tr.module_times(
            events, win, "field_fn")}
        if run.field_shapes is not None and peak is not None:
            red["field_least"] = least_time(field_cost(run.field_shapes), peak)
        device.update(busy_s=b["busy_s"], window_s=b["window_s"])
        view = View(run, red, peak)
        metrics = {}
        for m in bm["per_layer"]:
            if not applies(m, name):
                continue
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                                 "metric_" + m["name"].replace(".", "_"))
            v = reader.read(view)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["metrics"] = metrics
        out["breakdown"] = {
            "device_ops": tr.top_ops(events, win),
            "idle_gaps": tr.idle_gaps(
                tr.union(iv for v in b["per_device"].values() for iv in v),
                win, host)}
        if "field_least" in red:
            run.notes["field_roofline_bound"] = red["field_least"]["bound"]
        # the traced run's own end-to-end numbers: the tracing overhead
        run.notes["end_to_end_traced"] = run.end_to_end
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    out["device"] = device
    run.notes["compiles_in_window"] = in_window
    out["notes"] = run.notes
    for c in run.checks:
        log(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
            f"{'ok' if c.ok else 'FAIL'}")
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in run.checks}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bm = load_benchmark()
    cell, cfg, traffic = cell_files(bm, args.workload)

    import jax

    devs = jax.devices()
    d0 = devs[0]
    log(f"device: platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devs)}")
    if d0.platform != "tpu":
        log(f"no TPU: JAX found platform {d0.platform!r}; the benchmark "
            "runs on the chip only")
        return 2
    if len(devs) < cell["chips"]:
        log(f"cell {args.workload} needs {cell['chips']} chips, JAX found "
            f"{len(devs)}")
        return 2
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if d0.device_kind not in peaks:
        log(f"device kind {d0.device_kind!r} is not in bench/peaks.json")
        return 2
    out = run_cell(args.workload, cell, cfg, traffic, args.seed,
                   args.seconds, bool(args.trace), peaks[d0.device_kind])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
