#!/usr/bin/env python3
"""Controls for the comparison that decides ``correct``.

A control is the plain reference put in the program's place with one
guarantee broken, and has to come out as not correct:

* invoke cells state a float32 field: :func:`field_lowp` is the
  reference recurrence computed in bfloat16 (the next precision below),
  read against the float64 reference by the same ``field_rel_err``;
* serving cells state exact answers in descending lexicographic order:
  :func:`top_paths_ascending` returns the first matches in ascending
  order, read by ``wrong_paths``.

    python3 bench/control.py --workload <cell> --seeds 1,2,3

runs the control on the chip at the cell's own size, on each seed, beside
the program's own reading of the same number (the jnp field on the start
partition), and prints one JSON line per seed.  The benchmark's runs do
not call it.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List, Tuple

import numpy as np

from reference import Ref, Trie, field_errors, rpq_strings


def field_lowp(ref: Ref, part, workload, k: int, dtype="bfloat16") -> Dict:
    """:meth:`Ref.field` with every array and every operation in
    ``dtype`` (accumulations included), on JAX's default device."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    trie = Trie([(rpq_strings(q, sm), f) for q, f, sm in workload])
    n = ref.n
    nl = len(ref.label_names)
    src = jnp.asarray(ref.src, jnp.int32)
    dst = jnp.asarray(ref.dst, jnp.int32)
    labels = jnp.asarray(ref.labels, jnp.int32)
    part = jnp.asarray(np.asarray(part), jnp.int32)
    cnt = np.bincount(ref.src * nl + ref.labels[ref.dst],
                      minlength=n * nl).reshape(n, nl)
    inv_cnt = jnp.asarray(1.0 / np.maximum(cnt, 1.0), dt)
    lab_vcount = np.bincount(ref.labels, minlength=nl)
    local = (part[src] == part[dst]).astype(dt)
    dst_lab = labels[dst]
    alpha = {}
    for s in trie.prefixes:
        if len(s) == 1:
            li = ref.lab_id[s[0]]
            prior = jnp.asarray(trie.p[s] / max(int(lab_vcount[li]), 1), dt)
            alpha[s] = jnp.where(labels == li, prior, jnp.zeros((), dt))
    mass = jnp.zeros(src.shape[0], dt)
    for s in trie.prefixes:
        if len(s) < 2:
            continue
        lc = ref.lab_id[s[-1]]
        contrib = (alpha[s[:-1]][src] * jnp.asarray(trie.cond_p(s), dt)
                   * inv_cnt[src, lc] * (dst_lab == lc).astype(dt))
        mass = mass + contrib
        alpha[s] = jax.ops.segment_sum(contrib * local, dst, num_segments=n)
    pr = jnp.zeros(n, dt)
    for s in trie.counted:
        pr = pr + alpha[s]
    ext = mass * (1 - local)
    extro_mass = jax.ops.segment_sum(ext, src, num_segments=n)
    extroversion = jnp.where(pr > 0, extro_mass / jnp.where(pr > 0, pr, 1),
                             jnp.zeros((), dt))
    ext_to = jax.ops.segment_sum(ext, src * k + part[dst],
                                 num_segments=n * k).reshape(n, k)
    f64 = lambda a: np.asarray(a.astype(jnp.float32), np.float64)  # noqa: E731
    return {"pr": f64(pr), "extroversion": f64(extroversion),
            "ext_to": f64(ext_to),
            "total": float(f64(extro_mass).sum())}


def top_paths_ascending(ref: Ref, rpq: str, star_max: int,
                        max_results: int) -> List[Tuple[int, ...]]:
    """The first ``max_results`` matches in ascending order: the served
    answers with their order guarantee broken."""
    return ref.top_paths(rpq, star_max, max_results, descending=False)


def main(argv=None) -> int:
    import run as harness
    from common import run_graph, workload_of

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("no TPU; the control runs on the chip only", file=sys.stderr)
        return 2
    from repro.core.rpq import parse_rpq
    from repro.core.taper import Taper
    from repro.core.visitor import extroversion_field
    from repro.graphs.graph import LabelledGraph
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    _, cfg, traffic = harness.cell_files(harness.load_benchmark(),
                                         args.workload)
    wl = workload_of(cfg)
    k = cfg["k"]
    for seed in (int(s) for s in args.seeds.split(",")):
        labels, edges, part = run_graph(cfg, seed)
        n = labels.shape[0]
        if traffic["driver"] == "serve_invoke":
            ref = Ref(n, labels, edges, cfg["graph"]["labels"])
            mr = traffic["max_results_per_query"]
            differs = [top_paths_ascending(ref, q, sm, mr)
                       != ref.top_paths(q, sm, mr) for q, _, sm in wl]
            freqs = np.asarray([f for _, f, _ in wl])
            picks = np.random.default_rng([seed, 1]).choice(
                len(wl), size=1000, p=freqs / freqs.sum())
            print(json.dumps({"seed": seed, "control_ascending": {
                "wrong_paths_per_1000": int(sum(differs[i] for i in picks))}}),
                flush=True)
            continue
        g = LabelledGraph.from_undirected_edges(n, labels, edges,
                                                cfg["graph"]["labels"])
        taper = Taper(g, k)
        arrays = taper.build_trie(
            [(parse_rpq(q), f) for q, f, _ in wl]).compile(g.label_names)
        fld = extroversion_field(g, arrays, part, k, _precomputed={})
        prog = {"pr": fld.pr, "extroversion": fld.extroversion,
                "ext_to": fld.ext_to, "total": fld.total_extroversion}
        del taper, g, fld
        ref = Ref(n, labels, edges, cfg["graph"]["labels"])
        want = ref.field(part, wl, k)
        ctl = field_lowp(ref, part, wl, k)
        print(json.dumps({
            "seed": seed,
            "program": field_errors(prog, want),
            "control_bf16": field_errors(ctl, want)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
