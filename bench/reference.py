"""The benchmark's plain reference: basic graph patterns over id triples.

Independent of the system under test: it parses the benchmark's own
query texts (PREFIX, SELECT, one WHERE group of triple patterns) and
evaluates them with NumPy over the generator's encoded triples, with bag
semantics and no entailment, as SPARQL defines a basic graph pattern.
Joins sort one side by its packed key and find each row's matches by
binary search.

`key_bits` is the width at which term ids are compared (21 bits holds
every id of the configurations here; the check raises otherwise). The
control passes 16: the same evaluation with ids compared as int16, the
narrower key a later change could be tempted to pack joins into.
"""
from __future__ import annotations

import re

import numpy as np

ID_BITS = 21

_TOKEN = re.compile(r'\s*(<[^>\s]*>|"[^"]*"|\?\w+|[A-Za-z_][\w\-]*:[\w\-]*|'
                    r'\{|\}|\.|\*|a\b|PREFIX|SELECT|WHERE)', re.IGNORECASE)
RDF_TYPE = "<http://www.w3.org/1999/02/22-rdf-syntax-ns#type>"


def parse_bgp(text: str) -> tuple[list[str], list[tuple[str, str, str]]]:
    """(projected variables, triple patterns) of a SELECT over one BGP."""
    toks, pos = [], 0
    text = text.strip()
    while pos < len(text):
        m = _TOKEN.match(text, pos)
        if m is None:
            raise ValueError(f"cannot parse query at {text[pos:pos + 30]!r}")
        toks.append(m.group(1))
        pos = m.end()
        while pos < len(text) and text[pos].isspace():
            pos += 1
    prefixes: dict[str, str] = {}
    i = 0
    while toks[i].upper() == "PREFIX":
        prefixes[toks[i + 1][:-1]] = toks[i + 2][1:-1]
        i += 3
    if toks[i].upper() != "SELECT":
        raise ValueError("only SELECT queries")
    i += 1
    select = []
    while toks[i].upper() != "WHERE":
        select.append(toks[i])
        i += 1
    body = toks[i + 2:toks.index("}", i)]

    def term(t: str) -> str:
        if t == "a":
            return RDF_TYPE
        if t.startswith(("<", '"', "?")):
            return t
        pre, local = t.split(":", 1)
        return f"<{prefixes[pre]}{local}>"

    patterns, cur = [], []
    for t in body:
        if t == ".":
            continue
        cur.append(term(t))
        if len(cur) == 3:
            patterns.append(tuple(cur))
            cur = []
    if cur:
        raise ValueError("dangling triple pattern")
    if select == ["*"]:
        select = list(dict.fromkeys(t for p in patterns for t in p
                                    if t.startswith("?")))
    return select, patterns


class Graph:
    """Encoded triples grouped by predicate, for pattern scans."""

    def __init__(self, triples: np.ndarray, key_bits: int = ID_BITS):
        t = np.asarray(triples, np.int64).reshape(-1, 3)
        if len(t) and int(t.max()) >= 1 << ID_BITS:
            raise ValueError(f"term ids beyond {ID_BITS} bits")
        self.mask = (1 << key_bits) - 1
        order = np.argsort(t[:, 1] & self.mask, kind="stable")
        self.t = t[order]
        self.p_sorted = self.t[:, 1] & self.mask

    def scan(self, pattern, ids: dict[str, int]) -> tuple[list[str], np.ndarray]:
        """(variables, matching rows of their ids) of one triple pattern.
        A constant the dictionary lacks matches nothing."""
        rows = self.t
        if not pattern[1].startswith("?"):
            pid = ids.get(pattern[1], -1) & self.mask
            lo, hi = np.searchsorted(self.p_sorted, [pid, pid + 1])
            rows = rows[lo:hi]
            if pattern[1] not in ids:
                rows = rows[:0]
        keep = np.ones(len(rows), bool)
        vars_, cols = [], []
        for i, term in enumerate(pattern):
            if term.startswith("?"):
                if term in vars_:
                    keep &= (rows[:, i] & self.mask) == (
                        rows[:, cols[vars_.index(term)]] & self.mask)
                else:
                    vars_.append(term)
                    cols.append(i)
            elif i != 1:
                keep &= (rows[:, i] & self.mask) == (
                    ids.get(term, -1) & self.mask)
                if term not in ids:
                    keep[:] = False
        return vars_, rows[keep][:, cols]

    def _key(self, rows: np.ndarray, cols: list[int]) -> np.ndarray:
        k = np.zeros(len(rows), np.int64)
        for c in cols:
            k = (k << ID_BITS) | (rows[:, c] & self.mask)
        return k

    def join(self, a, b):
        va, ra = a
        vb, rb = b
        shared = [v for v in va if v in vb]
        extra = [i for i, v in enumerate(vb) if v not in va]
        if not shared:
            ia = np.repeat(np.arange(len(ra)), len(rb))
            ib = np.tile(np.arange(len(rb)), len(ra))
        else:
            ka = self._key(ra, [va.index(v) for v in shared])
            kb = self._key(rb, [vb.index(v) for v in shared])
            order = np.argsort(kb, kind="stable")
            kb_sorted = kb[order]
            lo = np.searchsorted(kb_sorted, ka, "left")
            hi = np.searchsorted(kb_sorted, ka, "right")
            n = hi - lo
            ia = np.repeat(np.arange(len(ra)), n)
            starts = np.repeat(lo - (np.cumsum(n) - n), n)
            ib = order[starts + np.arange(int(n.sum()))]
        rows = np.concatenate([ra[ia], rb[ib][:, extra]], axis=1)
        return va + [vb[i] for i in extra], rows

    def bgp(self, patterns, ids: dict[str, int]):
        """All bindings of the BGP: (variables, rows), every variable kept.
        Joins follow the smallest connected pattern first."""
        scans = [self.scan(p, ids) for p in patterns]
        left = list(range(len(scans)))
        first = min(left, key=lambda i: len(scans[i][1]))
        cur = scans[first]
        left.remove(first)
        while left:
            linked = [i for i in left if set(scans[i][0]) & set(cur[0])]
            nxt = min(linked or left, key=lambda i: len(scans[i][1]))
            cur = self.join(cur, scans[nxt])
            left.remove(nxt)
        return cur


def evaluate(graph: Graph, text: str, ids: dict[str, int]):
    """(projected variables, all bindings (vars, rows)) of a query."""
    select, patterns = parse_bgp(text)
    return select, graph.bgp(patterns, ids)


def project(select, bindings) -> np.ndarray:
    vars_, rows = bindings
    return rows[:, [vars_.index(v) for v in select]]


def canonical(rows: np.ndarray) -> np.ndarray:
    """Rows in a fixed order, so two bags compare with array equality."""
    rows = np.asarray(rows, np.int64)
    if len(rows) == 0:
        return rows
    return rows[np.lexsort(rows.T[::-1])]
