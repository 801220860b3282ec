"""Served RPQ traffic during a live TAPER invocation.

Set-up builds the graph and a ``ServingLoop`` (one worker, the default jnp
field), compiles the field, and serves the warm-up requests.  The window
opens with the request after them, which fires the loop's first
invocation; the arrivals start once it is in flight, and later
invocations follow back to back.  An open loop submits a fixed number of
requests, ``rate x seconds``, at Poisson due times, with queries at the
configuration's frequencies (:func:`common.arrivals`: every seed gets the
same gaps and the same count of each query, in its own order).  Each
request is timed from its due time to its answer; a refused request, or
one not answered ``common.ANSWER_WAIT_S`` after the window closed, counts
at that moment.  ``served_qps`` counts only the answers given by the
window's close.  After the window the invocation in flight is stopped at
its next iteration boundary, and the last field it computed, with the
partition its swap produced, is compared with the reference as well.
"""
from __future__ import annotations

import time

import numpy as np

import common
from common import (Check, Run, arrivals, balance_excess, log, percentile,
                    run_graph, workload_of)
from reference import Ref, crossings, field_errors


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.rate = (ctx.rate if ctx.rate is not None
                     else float(ctx.traffic["rate_rps"]))

    def setup(self) -> None:
        from repro.core.rpq import parse_rpq
        from repro.core.taper import TaperConfig
        from repro.graphs.graph import LabelledGraph
        from repro.serve.loop import ServeLoopConfig, ServingLoop

        ctx, cfg, tr = self.ctx, self.ctx.cfg, self.ctx.traffic
        self.labels, self.edges, self.part0 = run_graph(cfg, ctx.seed, ctx.n)
        n = self.labels.shape[0]
        g = LabelledGraph.from_undirected_edges(
            n, self.labels, self.edges, cfg["graph"]["labels"])
        self.workload = workload_of(cfg)
        self.queries = [parse_rpq(q) for q, _, _ in self.workload]
        self.freqs = np.asarray([f for _, f, _ in self.workload])
        self.freqs = self.freqs / self.freqs.sum()
        warm_n = tr["warmup_requests"]
        # the invocation fires on the request after the warm-up, so that the
        # field can first be compiled for the trie the invocation will build
        self.scfg = ServeLoopConfig(
            micro_batch=tr["micro_batch"],
            max_results_per_query=tr["max_results_per_query"],
            n_workers=tr["n_workers"],
            first_invocation_after=warm_n + 1,
            min_requests_between_invocations=0,
            trace_sample_rate=1.0 if ctx.trace else 0.0)
        self.loop = loop = ServingLoop(g, cfg["k"], self.part0, TaperConfig(),
                                       config=self.scfg)
        # the (query, frequency) list of every invocation begun, so that the
        # last field computed can be matched to the trie it was computed for
        self.inv_workloads = []
        begin = loop.ot.begin_invocation

        def begin_recorded(*a, **kw):
            pending = begin(*a, **kw)
            if pending is not None:
                self.inv_workloads.append(pending.workload)
            return pending

        loop.ot.begin_invocation = begin_recorded
        # seeds that differ by one draw unrelated arrivals
        self.rng = np.random.default_rng([ctx.seed, 1])
        loop.start()
        with ctx.annotate("bench.warmup_requests"):
            warm = self.rng.choice(len(self.queries), size=warm_n,
                                   p=self.freqs)
            for i in range(0, warm_n, self.scfg.micro_batch):
                self._serve([self.queries[j]
                             for j in warm[i:i + self.scfg.micro_batch]])
        # the trie's node order follows the order in which the loop's
        # sketch first saw each query: compile the field for that trie, on
        # a partition of the same shape that is not the start, so that the
        # first invocation evaluates the start's field on the device
        taper = loop.ot.taper
        wl = loop.ot.sketch.workload(loop.ot.policy.min_freq)
        with ctx.annotate("bench.warmup_field"):
            taper.field((self.part0 + 1) % cfg["k"],
                        taper.build_trie(wl).compile(g.label_names))
        self.trigger = self.queries[int(warm[-1])]
        log(f"graph n={g.n} m={g.m} k={cfg['k']}; offered rate "
            f"{self.rate:.3f} req/s")

    def _start_invocation(self) -> None:
        """Serve the request after the warm-up, which fires the loop's first
        invocation, and wait until it is in flight."""
        with self.ctx.annotate("bench.first_invocation"):
            self._serve([self.trigger])
            deadline = time.monotonic() + 60.0
            while not self.loop.invocation_in_flight:
                if time.monotonic() > deadline:
                    raise RuntimeError("the first invocation did not start")
                time.sleep(0.001)

    def _serve(self, queries) -> None:
        tickets = [self.loop.submit(q) for q in queries]
        for t in tickets:
            if not t.accepted or not t.wait(120.0):
                raise RuntimeError("a warm-up request was not served")

    def window(self) -> None:
        ctx, loop = self.ctx, self.loop
        if not self.inv_workloads:
            self._start_invocation()
        count = int(round(self.rate * ctx.seconds))
        offsets, picks = arrivals(ctx.seconds, count, self.freqs, self.rng)
        self.picks = picks
        self.parts = [loop.part]
        self.tickets, lateness = [], np.zeros(count)
        m = loop.metrics
        self.c0 = (m.frontier_rows, m.batches)
        self.w0 = time.monotonic()
        t0 = time.perf_counter()
        self.t0 = t0
        self.due = t0 + offsets
        with ctx.annotate("bench.serve_window"):
            for i in range(count):
                wait = self.due[i] - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                lateness[i] = time.perf_counter() - self.due[i]
                self.tickets.append(loop.submit(self.queries[picks[i]]))
                if loop.part is not self.parts[-1]:
                    self.parts.append(loop.part)
            rest = t0 + ctx.seconds - time.perf_counter()
            if rest > 0:
                time.sleep(rest)
        self.close = time.perf_counter()
        self.w1 = time.monotonic()
        self.lateness = lateness

    def finish(self) -> Run:
        ctx, loop = self.ctx, self.loop
        give_up = self.close + common.ANSWER_WAIT_S
        for t in self.tickets:
            if t.accepted:
                t.wait(max(0.0, give_up - time.perf_counter()))
        if loop.part is not self.parts[-1]:
            self.parts.append(loop.part)
        m = loop.metrics
        rows, batches = m.frontier_rows - self.c0[0], m.batches - self.c0[1]
        lat, answered, by_close, never = [], 0, 0, 0
        for due, t in zip(self.due, self.tickets):
            if t.accepted and t.done.is_set():
                answered += 1
                by_close += int(t.submitted_s + t.latency_s <= self.close)
                lat.append(t.submitted_s + t.latency_s - due)
            else:
                never += int(t.accepted)
                lat.append(give_up - due)
        lat = np.asarray(lat)
        late = self.lateness
        log(f"generator lateness: median {np.median(late) * 1e3:.3f} ms, "
            f"p99 {percentile(late, 99) * 1e3:.3f} ms, "
            f"max {late.max() * 1e3:.3f} ms over {late.size} submissions")
        log(f"latency from due time: median {np.median(lat) * 1e3:.3f} ms, "
            f"p99 {percentile(lat, 99) * 1e3:.3f} ms, answered {answered} "
            f"of {lat.size}, {by_close} by the close, refused "
            f"{sum(not t.accepted for t in self.tickets)}")
        # every request is answered, so no batch is served and no new
        # invocation can fire; the watchdog stops the one in flight at its
        # next iteration boundary
        loop.cfg.invocation_timeout_s = 0.0
        deadline = time.monotonic() + 300.0
        while loop.invocation_in_flight and time.monotonic() < deadline:
            time.sleep(0.01)
        try:
            stats = loop.stop()
        except TimeoutError:
            stats = loop.stats()
        # the abandoned run exits at its next iteration boundary, after the
        # field of the partition its last swap produced
        for th in loop._abandoned:
            th.join(max(0.0, deadline - time.monotonic()))
        taper = loop.ot.taper
        memo_key, fld = taper._field_memo
        part_last = np.frombuffer(memo_key[3], dtype=np.int32)
        # the trie of the last field: the newest begun invocation's whose
        # probabilities it was keyed on
        fld_wl = None
        for wl in self.inv_workloads:
            arrays = taper.build_trie(wl).compile(taper.g.label_names)
            if (arrays.p.tobytes(), arrays.cond_p.tobytes()) == memo_key[1:3]:
                fld_wl = wl
        spans = loop.obs.tracer.spans() if ctx.trace else []
        log("loop: " + ", ".join(f"{k}={stats[k]}" for k in (
            "completed", "batches", "invocations", "invocation_failures",
            "watchdog_aborts", "healthy", "field_backend", "worker_error")))

        t = time.perf_counter()
        ref = Ref(self.labels.shape[0], self.labels, self.edges,
                  ctx.cfg["graph"]["labels"])
        k = ctx.cfg["k"]
        mr = self.scfg.max_results_per_query
        want = [ref.top_paths(q, sm, mr) for q, _, sm in self.workload]
        cross = [[crossings(p, part) for part in self.parts] for p in want]
        wrong_paths = wrong_ipt = 0
        for qi, tk in zip(self.picks, self.tickets):
            if not (tk.accepted and tk.done.is_set()):
                continue
            wrong_paths += int(tk.paths != want[qi])
            wrong_ipt += int(tk.ipt not in cross[qi])
        if fld_wl is None:
            errs = {"no_trie_matches_the_last_field": float("nan")}
        else:
            text = {q: w for q, w in zip(self.queries, self.workload)}
            errs = field_errors(
                {"pr": fld.pr, "extroversion": fld.extroversion,
                 "ext_to": fld.ext_to, "total": fld.total_extroversion},
                ref.field(part_last, [(text[q][0], f, text[q][2])
                                      for q, f in fld_wl], k))
        log("field errors vs float64 reference: "
            + ", ".join(f"{n}={v:.4e}" for n, v in errs.items()))
        log(f"reference answers: {[len(w) for w in want]} paths per query, "
            f"{len(self.parts)} partitions served; took "
            f"{time.perf_counter() - t:.1f} s")
        # every partition served, and the one the invocation's last swap
        # produced, stays inside the balance band, or no further outside it
        # than the start partition was
        excess = max(balance_excess(np.asarray(p), k)
                     for p in self.parts + [part_last]) - balance_excess(
                         self.part0, k)
        errors = int(bool(stats["worker_error"]))
        return Run(
            attempted=len(self.tickets),
            failed=len(self.tickets) - answered,
            end_to_end={"query_p99_ms": percentile(lat, 99) * 1e3,
                        "served_qps": by_close / ctx.seconds},
            checks=[Check("wrong_paths", wrong_paths, 0),
                    Check("wrong_ipt", wrong_ipt, 0),
                    Check("never_answered", never, 0),
                    Check("field_rel_err", max(errs.values()),
                          ctx.cfg["limits"]["field_rel_err"]),
                    Check("balance_excess", excess, 0),
                    Check("worker_errors", errors, 0)],
            spans=spans,
            counters={"frontier_rows": rows, "batches": batches},
            notes={"window_monotonic": [self.w0, self.w1],
                   "lateness_p99_ms": percentile(late, 99) * 1e3})
