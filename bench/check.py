"""The comparison that decides `correct`.

Every read's row count, and the rows of a seeded share of them, are
compared with the reference's answer at a store state the read may have
seen: snapshot consistency lets a read see any state from the last write
acknowledged before it was sent to the last write sent before it was
answered. Writes are checked by their acknowledgement and, once the
window has closed, by the store's whole triple set against the model.

All limits are 0: the comparison is exact (see PERF.md for the readings
they were set from).
"""
from __future__ import annotations

import numpy as np

import reference
import uba

LIMITS = {
    "requests_failed": 0,
    "row_counts_wrong": 0,
    "answers_wrong": 0,
    "writes_wrong": 0,
    "store_rows_wrong": 0,
}


class Model:
    """The benchmark's own model of the triple set: the generated data
    plus every undergraduate the schedule inserts, in its own term ids."""

    def __init__(self, data: uba.Data, students, key_bits=reference.ID_BITS):
        self.data = data
        self.term_id = data.term_ids()
        self.n_base_terms = len(data.terms)
        self.terms = list(data.terms)
        self.students = students
        rows = []
        for st in students:
            rows.append([[self._id(t) for t in tr] for tr in st.triples])
        self.student_rows = [np.asarray(r, np.int64).reshape(-1, 3)
                             for r in rows]
        self.student_of = np.full(len(self.terms), -1, np.int64)
        for i, st in enumerate(students):
            self.student_of[self.term_id[st.triples[0][0]]] = i
        extra = (np.concatenate(self.student_rows) if students
                 else np.zeros((0, 3), np.int64))
        self.graph = reference.Graph(
            np.concatenate([data.triples.astype(np.int64), extra]), key_bits)
        self._bindings: dict[str, tuple] = {}
        self._answers: dict[tuple, np.ndarray] = {}

    def _id(self, term: str) -> int:
        i = self.term_id.get(term)
        if i is None:
            i = self.term_id[term] = len(self.terms)
            self.terms.append(term)
        return i

    def bindings(self, text: str):
        b = self._bindings.get(text)
        if b is None:
            select, bind = reference.evaluate(self.graph, text, self.term_id)
            vars_, rows = bind
            stud = self.student_of[rows] if rows.size else rows
            b = self._bindings[text] = (select, vars_, rows, stud)
        return b

    def answer(self, text: str, live: np.ndarray) -> np.ndarray:
        """Canonical rows of `text` with only the `live` students present."""
        select, vars_, rows, stud = self.bindings(text)
        if rows.size and self.students:
            ok = np.all((stud < 0) | live[np.clip(stud, 0, None)], axis=1)
            rows = rows[ok]
        return reference.canonical(reference.project(select, (vars_, rows)))

    def relevant(self, text: str) -> np.ndarray:
        """Students that any binding of `text` involves."""
        stud = self.bindings(text)[3]
        return np.unique(stud[stud >= 0]) if stud.size else np.zeros(0, int)

    def encode_rows(self, select, rows) -> np.ndarray:
        tid = self.term_id
        flat = [tid.get(r.get(v), -1) for r in rows for v in select]
        return reference.canonical(
            np.asarray(flat, np.int64).reshape(len(rows), len(select)))


def states(writes, setup_n: int, n_students: int):
    """Live-student masks after each prefix of the window's writes."""
    live = np.zeros(n_students, bool)
    live[:setup_n] = True
    out = [live.copy()]
    for w in writes:
        live[w.student.index] = w.name == "insert"
        out.append(live.copy())
    return out


def check(model: Model, log, setup_writes, store_rows=None) -> dict:
    """Numbers compared, by name: (value, limit)."""
    reads = [r for r in log if r.kind == "read"]
    writes = sorted((r for r in log if r.kind == "write"),
                    key=lambda r: r.t_send)
    failed = sum(not r.ok for r in log) + sum(not r.ok for r in setup_writes)
    live = states(writes, len(setup_writes), len(model.students))
    w_send = np.array([w.t_send for w in writes])
    w_done = np.array([w.t_done for w in writes])
    counts_wrong = answers_wrong = 0
    for r in reads:
        if not r.ok:
            continue
        k_lo = int(np.sum(w_done <= r.t_send))
        k_hi = int(np.sum(w_send < r.t_done))
        rel = model.relevant(r.text)
        seen, answers = set(), []
        for k in range(k_lo, k_hi + 1):
            key = (r.text,) + tuple(live[k][rel])
            if key not in seen:
                seen.add(key)
                a = model._answers.get(key)
                if a is None:
                    a = model._answers[key] = model.answer(r.text, live[k])
                answers.append(a)
        if r.n_rows not in {len(a) for a in answers}:
            counts_wrong += 1
        if r.check and r.rows is not None:
            select = model.bindings(r.text)[0]
            got = model.encode_rows(select, r.rows)
            if not any(got.shape == a.shape and np.array_equal(got, a)
                       for a in answers):
                answers_wrong += 1
    out = {
        "requests_failed": failed,
        "row_counts_wrong": counts_wrong,
        "answers_wrong": answers_wrong,
    }
    if writes or setup_writes:
        wrong = 0
        for w in list(setup_writes) + writes:
            n = len(w.student.triples)
            want = (n, 0) if w.name == "insert" else (0, n)
            wrong += w.ok and tuple(w.ack) != want
        out["writes_wrong"] = int(wrong)
    if store_rows is not None:
        final = live[-1]
        model_rows = np.concatenate(
            [model.data.triples.astype(np.int64)]
            + [model.student_rows[i] for i in np.flatnonzero(final)])
        out["store_rows_wrong"] = _sym_diff(model_rows, store_rows)
    return {k: (int(v), LIMITS[k]) for k, v in out.items()}


def _sym_diff(a: np.ndarray, b: np.ndarray) -> int:
    def pack(t):
        t = np.asarray(t, np.int64)
        return np.sort((t[:, 0] << 42) | (t[:, 1] << 21) | t[:, 2])

    pa, pb = pack(a), pack(b)
    common = np.intersect1d(pa, pb, assume_unique=False)
    return int(len(pa) + len(pb) - 2 * len(common))


def store_rows_in_model_ids(model: Model, store) -> np.ndarray:
    """The store's effective triples, re-encoded into the model's ids (the
    program numbers terms the write path added in its own order)."""
    t = np.asarray(store.triples, np.int64)
    d = store.dictionary
    n = len(d)
    remap = np.arange(max(n, model.n_base_terms), dtype=np.int64)
    for i in range(model.n_base_terms, n):
        remap[i] = model.term_id.get(d.decode(i), -1)
    return remap[t]
