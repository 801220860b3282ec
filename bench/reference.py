"""Plain float64 reference of what the benchmark checks, in numpy alone.

It imports nothing of the program and takes nothing the program made: it
parses the query texts itself, builds its own prefix trie and
probabilities, its own adjacency from the generated edge list, and
evaluates in float64:

* ``Ref.field`` - the extroversion-field recurrence (paper section 5.4;
  the program's ``core/visitor.py`` docstring states it);
* ``Ref.workload_ipt`` - expected inter-partition traversals of a
  workload (paper section 6.1), from per-edge traversal counts;
* ``Ref.top_paths`` - the first ``max_results`` matches of a query in
  descending lexicographic order of their vertex tuples, which is the
  serving contract, and the partition crossings on them.
"""
from __future__ import annotations

import re
from typing import Dict, FrozenSet, List, Sequence, Tuple

import numpy as np

Strings = FrozenSet[Tuple[str, ...]]

_TOKEN = re.compile(r"\s*([A-Za-z_][A-Za-z0-9_]*|[().|+*·])")


def rpq_strings(text: str, star_max: int, max_len: int = 32) -> Strings:
    """Label strings of an RPQ text: ``.`` concatenates, ``|`` and ``+``
    unite, ``*`` repeats 0..``star_max`` times; empty strings and strings
    longer than ``max_len`` are dropped."""
    toks = []
    pos = 0
    text = text.strip()
    while pos < len(text):
        m = _TOKEN.match(text, pos)
        if m is None:
            raise ValueError(f"cannot parse {text!r} at {pos}")
        toks.append("." if m.group(1) == "·" else m.group(1))
        pos = m.end()
    i = 0

    def peek():
        return toks[i] if i < len(toks) else ""

    def union() -> Strings:
        nonlocal i
        acc = concat()
        while peek() in ("|", "+"):
            i += 1
            acc = acc | concat()
        return acc

    def concat() -> Strings:
        nonlocal i
        acc = postfix()
        while peek() and peek() not in (")", "|", "+"):
            if peek() == ".":
                i += 1
            nxt = postfix()
            acc = frozenset(a + b for a in acc for b in nxt)
        return acc

    def postfix() -> Strings:
        nonlocal i
        base = atom()
        while peek() == "*":
            i += 1
            reps, acc = frozenset({()}), frozenset({()})
            for _ in range(star_max):
                reps = frozenset(a + b for a in reps for b in base)
                acc = acc | reps
            base = acc
        return base

    def atom() -> Strings:
        nonlocal i
        tok = peek()
        if tok == "(":
            i += 1
            inner = union()
            if peek() != ")":
                raise ValueError(f"missing ')' in {text!r}")
            i += 1
            return inner
        if not tok or tok in ").|+*":
            raise ValueError(f"unexpected {tok!r} in {text!r}")
        i += 1
        return frozenset({(tok,)})

    out = union()
    if i != len(toks):
        raise ValueError(f"trailing tokens in {text!r}")
    return frozenset(s for s in out if 0 < len(s) <= max_len)


class Trie:
    """Prefix trie of a weighted workload with the node probabilities of
    paper section 4.1: within a query, the next label is uniform over the
    distinct next labels the query admits; ``p(node)`` is the
    frequency-weighted sum over queries."""

    def __init__(self, workload: Sequence[Tuple[Strings, float]]):
        total = sum(max(f, 0.0) for _, f in workload)
        owners: Dict[Tuple[str, ...], set] = {}
        for qi, (strings, f) in enumerate(workload):
            if f <= 0:
                continue
            for s in strings:
                for d in range(1, len(s) + 1):
                    owners.setdefault(s[:d], set()).add(qi)
        self.prefixes = sorted(owners, key=lambda s: (len(s), s))
        p = {(): 1.0}
        for pre in self.prefixes:
            p[pre] = 0.0
        for qi, (strings, f) in enumerate(workload):
            if f <= 0:
                continue
            mine = sorted((s for s in self.prefixes if qi in owners[s]),
                          key=len)
            kids: Dict[Tuple[str, ...], List[Tuple[str, ...]]] = {}
            for s in mine:
                kids.setdefault(s[:-1], []).append(s)
            pr = {(): 1.0}
            for s in mine:
                pr[s] = pr[s[:-1]] / len(kids[s[:-1]])
                p[s] += (f / total) * pr[s]
        self.p = p
        self.max_depth = max((len(s) for s in self.prefixes), default=0)
        has_child = {s[:-1] for s in self.prefixes}
        self.counted = [s for s in self.prefixes
                        if len(s) < self.max_depth and s in has_child]

    def cond_p(self, s: Tuple[str, ...]) -> float:
        return self.p[s] / max(self.p[s[:-1]], 1e-30)


class Ref:
    """Reference views of one generated graph (module doc)."""

    def __init__(self, n: int, labels: np.ndarray, edges: np.ndarray,
                 label_names: Sequence[str]):
        e = np.asarray(edges, dtype=np.int64)
        e = e[e[:, 0] != e[:, 1]]
        sym = np.unique(np.concatenate([e[:, 0] * n + e[:, 1],
                                        e[:, 1] * n + e[:, 0]]))
        self.n = n
        self.src = sym // n
        self.dst = sym % n
        self.labels = np.asarray(labels, dtype=np.int64)
        self.label_names = list(label_names)
        self.lab_id = {s: i for i, s in enumerate(self.label_names)}
        self.row_ptr = np.concatenate(
            [[0], np.cumsum(np.bincount(self.src, minlength=n))])
        self._trav: Dict[Tuple[str, int], np.ndarray] = {}

    # -- the extroversion field --------------------------------------------
    def field(self, part: np.ndarray, workload, k: int) -> Dict[str, object]:
        """Float64 field of ``part`` under ``workload`` (a list of
        (rpq text, frequency)) with ``star_max`` from each entry's third
        element: per-vertex ``pr`` and ``extroversion``, the ``(n, k)``
        external mass per destination part ``ext_to``, and ``total``."""
        trie = Trie([(rpq_strings(q, sm), f) for q, f, sm in workload])
        n, src, dst = self.n, self.src, self.dst
        part = np.asarray(part, dtype=np.int64)
        nl = len(self.label_names)
        cnt = np.bincount(src * nl + self.labels[dst],
                          minlength=n * nl).reshape(n, nl)
        inv_cnt = 1.0 / np.maximum(cnt.astype(np.float64), 1.0)
        lab_vcount = np.bincount(self.labels, minlength=nl)
        local = part[src] == part[dst]
        dst_lab = self.labels[dst]
        col = {s: i for i, s in enumerate(trie.prefixes)}
        alpha = np.zeros((n, len(trie.prefixes)))
        for s in trie.prefixes:
            if len(s) == 1:
                li = self.lab_id[s[0]]
                alpha[self.labels == li, col[s]] = (
                    trie.p[s] / max(int(lab_vcount[li]), 1))
        mass = np.zeros(src.shape[0])
        for s in trie.prefixes:
            if len(s) < 2:
                continue
            lc = self.lab_id[s[-1]]
            sel = np.nonzero(dst_lab == lc)[0]
            contrib = (alpha[src[sel], col[s[:-1]]] * trie.cond_p(s)
                       * inv_cnt[src[sel], lc])
            mass[sel] += contrib
            alpha[:, col[s]] += np.bincount(
                dst[sel], weights=contrib * local[sel], minlength=n)
        pr = alpha[:, [col[s] for s in trie.counted]].sum(axis=1)
        ext = mass * ~local
        extro_mass = np.bincount(src, weights=ext, minlength=n)
        extroversion = np.where(pr > 1e-30,
                                extro_mass / np.maximum(pr, 1e-30), 0.0)
        ext_to = np.bincount(src * k + part[dst], weights=ext,
                             minlength=n * k).reshape(n, k)
        return {"pr": pr, "extroversion": extroversion, "ext_to": ext_to,
                "total": float(extro_mass.sum())}

    # -- expected inter-partition traversals --------------------------------
    def traversals(self, rpq: str, star_max: int) -> np.ndarray:
        """Per-edge count of traversals a full evaluation of the query
        makes: a path whose label string is a prefix of one of the query's
        strings is extended over every edge to a next admissible label."""
        key = (rpq, star_max)
        if key not in self._trav:
            prefixes = sorted({s[:d] for s in rpq_strings(rpq, star_max)
                               for d in range(1, len(s) + 1)},
                              key=lambda s: (len(s), s))
            n, src, dst = self.n, self.src, self.dst
            dst_lab = self.labels[dst]
            cnt = {}
            trav = np.zeros(src.shape[0])
            for s in prefixes:
                if len(s) == 1:
                    cnt[s] = (self.labels == self.lab_id[s[0]]).astype(float)
                    continue
                sel = np.nonzero(dst_lab == self.lab_id[s[-1]])[0]
                contrib = cnt[s[:-1]][src[sel]]
                trav[sel] += contrib
                cnt[s] = np.bincount(dst[sel], weights=contrib, minlength=n)
            self._trav[key] = trav
        return self._trav[key]

    def workload_ipt(self, part: np.ndarray, workload) -> float:
        """Frequency-weighted expected ipt per query execution."""
        part = np.asarray(part)
        cut = part[self.src] != part[self.dst]
        return float(sum(f * self.traversals(q, sm)[cut].sum()
                         for q, f, sm in workload))

    # -- served answers -----------------------------------------------------
    def top_paths(self, rpq: str, star_max: int, max_results: int,
                  descending: bool = True) -> List[Tuple[int, ...]]:
        """The first ``max_results`` matches in descending (or ascending)
        lexicographic order of their vertex tuples.  A match is a path
        whose label string is one of the query's strings and none of whose
        proper prefixes is (a match is never extended)."""
        targets = {tuple(self.lab_id[x] for x in s)
                   for s in rpq_strings(rpq, star_max)
                   if all(x in self.lab_id for x in s)}
        prefixes = {t[:d] for t in targets for d in range(1, len(t) + 1)}
        max_len = max((len(t) for t in targets), default=0)
        out: List[Tuple[int, ...]] = []

        def walk(path, labs):
            if labs in targets:
                out.append(tuple(path))
                return
            if len(labs) >= max_len:
                return
            v = path[-1]
            nbrs = self.dst[self.row_ptr[v]:self.row_ptr[v + 1]]
            for u in sorted(nbrs.tolist(), reverse=descending):
                nl = labs + (int(self.labels[u]),)
                if nl in prefixes:
                    path.append(u)
                    walk(path, nl)
                    path.pop()
                    if len(out) >= max_results:
                        return

        firsts = {t[0] for t in targets}
        order = range(self.n - 1, -1, -1) if descending else range(self.n)
        for v in order:
            if len(out) >= max_results:
                break
            if int(self.labels[v]) in firsts:
                walk([v], (int(self.labels[v]),))
        return out[:max_results]


def crossings(paths: Sequence[Tuple[int, ...]], part: np.ndarray) -> int:
    """Consecutive vertex pairs of the paths that lie in different parts."""
    part = np.asarray(part)
    return int(sum(np.count_nonzero(part[list(p[1:])] != part[list(p[:-1])])
                   for p in paths if len(p) > 1))


def rel_err(x, ref) -> float:
    """Largest error relative to the reference's magnitude: per column for
    a 2-D array, normwise for a vector, relative for a scalar."""
    x = np.asarray(x, np.float64)
    ref = np.asarray(ref, np.float64)
    if ref.ndim == 0:
        return float(abs(x - ref) / max(abs(ref), 1e-30))
    if ref.ndim == 1:
        return float(np.abs(x - ref).max() / max(np.abs(ref).max(), 1e-30))
    scale = np.maximum(np.abs(ref).max(axis=0), 1e-30)
    return float((np.abs(x - ref).max(axis=0) / scale).max())


def field_errors(got: Dict, want: Dict) -> Dict[str, float]:
    """:func:`rel_err` of each part of a field (``pr``, ``extroversion``,
    ``ext_to``, ``total``) against the reference's."""
    return {key: rel_err(got[key], want[key])
            for key in ("pr", "extroversion", "ext_to", "total")}
