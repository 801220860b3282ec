#!/usr/bin/env python3
"""Sweep of offered rates for a serving cell, with an invocation in flight.

    python3 bench/knee.py --config <config> --traffic <mix> --seed <n> --seconds <s> --rates 20,40,60

One set-up, then one open-loop window per rate, back to back, each timed
as the cell's own window is.  Prints one JSON line per rate: p50 and p99
from due time, the share answered by the window's close, and the
generator's lateness.  The knee is the highest rate whose p99 stays near
the low rates' and whose requests are answered as they come; a serving
mix offers a fixed share of it (``rate_rps`` in its traffic file).  Used
once, by hand, to set that number; the benchmark's runs do not call it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

import run as harness
from common import log, percentile


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True,
                    help="name of a file under bench/configs/")
    ap.add_argument("--traffic", required=True,
                    help="name of a serving mix under bench/traffic/")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]
    import jax

    if jax.devices()[0].platform != "tpu":
        log("no TPU; the sweep runs on the chip only")
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = json.loads((harness.BENCH / "configs" /
                      f"{args.config}.json").read_text())
    traffic = json.loads((harness.BENCH / "traffic" /
                          f"{args.traffic}.json").read_text())
    ctx = harness.Ctx(cfg, traffic, args.seed, args.seconds, False,
                      rate=rates[0])
    drv = harness.load_module(
        harness.BENCH / "drivers" / f"{traffic['driver']}.py",
        "driver").Driver(ctx)
    drv.setup()
    for r in rates:
        drv.rate = r
        drv.window()
        answered_by_close = sum(
            t.accepted and t.done.is_set() for t in drv.tickets)
        for t in drv.tickets:
            if t.accepted:
                t.wait(60.0)
        lat = np.asarray([
            t.submitted_s + t.latency_s - d if t.accepted and t.done.is_set()
            else np.inf for d, t in zip(drv.due, drv.tickets)])
        print(json.dumps({
            "rate": r, "requests": int(lat.size),
            "p50_ms": percentile(lat[np.isfinite(lat)], 50) * 1e3,
            "p99_ms": (percentile(lat, 99) * 1e3
                       if np.isfinite(lat).all() else None),
            "answered_by_close": answered_by_close / max(lat.size, 1),
            "refused": int(sum(not t.accepted for t in drv.tickets)),
            "lateness_p99_ms": percentile(drv.lateness, 99) * 1e3,
            "invocation_in_flight": drv.loop.invocation_in_flight,
            "invocations_committed": drv.loop.stats()["invocations"]}),
            flush=True)
        time.sleep(1.0)
    drv.loop.cfg.invocation_timeout_s = 0.0
    while drv.loop.invocation_in_flight:
        time.sleep(0.01)
    try:
        drv.loop.stop()
    except TimeoutError:
        pass
    return 0


if __name__ == "__main__":
    sys.exit(main())
