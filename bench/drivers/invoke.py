"""Maintenance path alone: back-to-back one-iteration TAPER invocations.

Set-up builds the graph, the ``Taper`` and the workload trie, and compiles
the field.  Each call of ``Taper.invoke(part, trie, max_iterations=1)``
continues from the last partition; after an iteration that moves nothing,
cuts the objective by less than ``converge_rel_tol``, or is the
``iterations_per_start``-th since the start (the traffic mix's, else
``max_iterations``), the next one restarts from the start partition.  The
iteration still running when the window's time is up finishes and counts.

With several iterations per start, set-up computes the start partition's
field, so every iteration of the window is one host swap plus one device
field.  With one, every iteration is the same work, a whole one-iteration
invocation from the start (its field, a swap, the new field): set-up then
compiles the field on a shifted copy of the start, so that the window's
first invocation evaluates the start's field as the later ones do.
"""
from __future__ import annotations

import time

import numpy as np

from common import Check, Run, balance_excess, log, run_graph, workload_of
from reference import Ref, field_errors


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx

    def setup(self) -> None:
        from repro.core.rpq import parse_rpq
        from repro.core.taper import Taper, TaperConfig
        from repro.graphs.graph import LabelledGraph

        ctx, cfg = self.ctx, self.ctx.cfg
        self.labels, self.edges, self.part0 = run_graph(cfg, ctx.seed, ctx.n)
        n = self.labels.shape[0]
        g = LabelledGraph.from_undirected_edges(
            n, self.labels, self.edges, cfg["graph"]["labels"])
        self.tcfg = TaperConfig()
        self.taper = Taper(g, cfg["k"], self.tcfg)
        self.tracer = None
        if ctx.trace:
            from repro.obs.trace import Tracer

            self.tracer = Tracer(capacity=1 << 16)
            self.taper.tracer = self.tracer
            self.taper.trace_ctx = self.tracer.new_trace(force=True)
        self.workload = workload_of(cfg)
        trie = self.taper.build_trie(
            [(parse_rpq(q), f) for q, f, _ in self.workload])
        self.arrays = trie.compile(g.label_names)
        self.per_start = int(ctx.traffic.get("iterations_per_start",
                                             self.tcfg.max_iterations))
        warm = (self.part0 if self.per_start > 1
                else (self.part0 + 1) % cfg["k"])
        with ctx.annotate("bench.warmup_field"):
            self.taper.field(warm, self.arrays)
        # the swap builds the graph's reverse-edge index on its first call;
        # build it here, so that the window's first iteration does not
        g.reverse_edge_index
        log(f"graph n={g.n} m={g.m} trie_nodes={self.arrays.n_nodes} "
            f"max_depth={self.arrays.max_depth} k={cfg['k']}")

    def window(self) -> None:
        ctx, taper, cfg = self.ctx, self.taper, self.tcfg
        self.t0 = time.perf_counter()
        self.w0 = time.monotonic()
        deadline = self.t0 + ctx.seconds
        part, since_start = self.part0, 0
        self.ends, self.excess, self.part_first = [], [], None
        while True:
            with ctx.annotate("bench.invoke"):
                rep = taper.invoke(part, self.arrays, max_iterations=1)
            self.ends.append(time.perf_counter())
            new = rep.final_part
            if self.part_first is None:
                self.part_first = new.copy()
            self.excess.append(balance_excess(new, self.ctx.cfg["k"],
                                              cfg.balance_eps))
            since_start += 1
            obj = rep.objective
            cut = (obj[0] - obj[-1]) / obj[0] if obj[0] > 0 else 0.0
            if (rep.iterations == 0 or cut < cfg.converge_rel_tol
                    or since_start >= self.per_start):
                part, since_start = self.part0, 0
            else:
                part = new
            if self.ends[-1] >= deadline:
                break
        self.w1 = time.monotonic()

    def finish(self) -> Run:
        ctx = self.ctx
        iters = len(self.ends)
        iteration_s = (self.ends[-1] - self.t0) / iters
        log(f"window: {iters} iterations, ends at "
            + ", ".join(f"{e - self.t0:.3f}" for e in self.ends) + " s")
        memo_key, fld = self.taper._field_memo
        part_last = np.frombuffer(memo_key[3], dtype=np.int32)
        spans = self.tracer.spans() if self.tracer is not None else []
        taper_k = self.ctx.cfg["k"]
        shapes = {"n": int(self.taper.g.n), "m": int(self.taper.g.m),
                  "k": taper_k, "n_labels": len(self.taper.g.label_names),
                  "depth_nodes": [int((self.arrays.depth == d).sum())
                                  for d in range(self.arrays.max_depth + 1)],
                  "n_nodes": int(self.arrays.n_nodes)}
        del self.taper
        t = time.perf_counter()
        ref = Ref(self.labels.shape[0], self.labels, self.edges,
                  ctx.cfg["graph"]["labels"])
        rf = ref.field(part_last, self.workload, taper_k)
        errs = field_errors({"pr": fld.pr, "extroversion": fld.extroversion,
                             "ext_to": fld.ext_to,
                             "total": fld.total_extroversion}, rf)
        log("field errors vs float64 reference: "
            + ", ".join(f"{k}={v:.4e}" for k, v in errs.items()))
        ipt0 = ref.workload_ipt(self.part0, self.workload)
        ipt1 = ref.workload_ipt(self.part_first, self.workload)
        ratio = ipt1 / ipt0
        log(f"reference ipt: start {ipt0!r}, after the first iteration "
            f"{ipt1!r}; reference took {time.perf_counter() - t:.1f} s")
        lim = ctx.cfg["limits"]
        return Run(
            attempted=iters, failed=0,
            end_to_end={"iteration_s": iteration_s, "ipt_ratio": ratio},
            checks=[Check("field_rel_err", max(errs.values()),
                          lim["field_rel_err"]),
                    Check("ipt_ratio_first", ratio, lim["ipt_ratio_first"]),
                    Check("balance_excess", float(max(self.excess)), 0.0)],
            spans=spans, field_shapes=shapes,
            notes={"window_monotonic": [self.w0, self.w1],
                   "iterations": iters})
