"""The general load generator: a traffic file's parameters in, a seeded
request schedule out, and the closed or open loop that sends it.

A traffic file (bench/traffic/<mix>.json) gives:
  loop           "closed" (clients that each wait for their answer) or
                 "open" (Poisson arrivals at `rate_per_s`, sent on
                 schedule whatever the server does)
  reads          query name -> weight (a whole number), names of
                 bench/queries/lubm.json
  constants      how a template's placeholders are drawn: {"dept":
                 {"zipf_s": s}} ranks departments by UBA numbering
                 (University0's first); {univ} is the university of the
                 drawn department
  write_share    share of requests that are writes (open loop only):
                 INSERT DATA of a new undergraduate in a department drawn
                 like {dept}, alternating with DELETE DATA of the oldest
                 live one
  check_share    share of answers compared row for row with the reference
                 (every answer's row count is compared)
  clients        closed loop: the number of clients
  workers        open loop: the threads that send reads (writes have one
                 thread of their own, in order)
What a run sends is drawn once from a fixed generator: the number of
requests and of writes, the multiset of arrival gaps, and the multiset of
(query, constant) reads, so every seed offers the same work. --seed draws
the order of all three and the undergraduates the writes carry; the same
seed gives the same schedule.
"""
from __future__ import annotations

import dataclasses
import json
import os
import queue
import threading
import time

import numpy as np

import uba

HERE = os.path.dirname(os.path.abspath(__file__))
MIX_SEED = 0  # the fixed generator of what every run sends
# An answer may come up to a minute past the window's close (it is late,
# and its latency counts the wait); one that has not come by then failed.
LATE_S = 60.0


def load_json(*parts) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def query_texts() -> dict[str, str]:
    q = load_json("queries", "lubm.json")
    return {k: q["prefix"] + v for k, v in q["queries"].items()}


@dataclasses.dataclass
class Student:
    """One undergraduate the write stream inserts: its triples as terms."""

    index: int
    dept: int
    triples: list[tuple[str, str, str]]

    def update_text(self, op: str) -> str:
        body = " .\n".join(f"{s} {p} {o}" for s, p, o in self.triples)
        return f"{op} DATA {{\n{body} .\n}}"


@dataclasses.dataclass
class Request:
    kind: str  # "read" | "write"
    name: str  # query name, or "insert" / "delete"
    text: str
    t_sched: float = 0.0  # seconds after the window opens (open loop)
    student: Student | None = None
    check: bool = False  # compare rows with the reference
    # filled in by the loop
    t_from: float = 0.0  # latency runs from here: send, or due time
    t_send: float = 0.0
    t_done: float = 0.0
    ok: bool = False
    error: str = ""
    n_rows: int = -1
    rows: list | None = None
    ack: tuple = ()  # (inserted, deleted) of a write


class Schedule:
    """The seeded requests of one run of a mix over one data set."""

    def __init__(self, traffic: dict, data: uba.Data, seed: int,
                 seconds: float, live_inserted: int = 0):
        self.traffic = traffic
        self.data = data
        self.seed = int(seed)
        self.texts = query_texts()
        n_dept = len(data.dept_uni)
        c = traffic.get("constants", {}).get("dept", {})
        s = float(c.get("zipf_s", 0.0))
        w = 1.0 / np.arange(1, n_dept + 1) ** s
        self.dept_p = w / w.sum()
        self.names = list(traffic["reads"])
        wr = np.array([traffic["reads"][n] for n in self.names], float)
        self.read_p = wr / wr.sum()
        self.next_student: dict[int, int] = {}
        self.n_students = 0
        self.setup_writes: list[Request] = []
        self.live: list[Student] = []
        self._wrng = np.random.default_rng([self.seed, 3])
        for _ in range(live_inserted):
            st = self._new_student()
            self.live.append(st)
            self.setup_writes.append(Request("write", "insert",
                                             st.update_text("INSERT"),
                                             student=st))
        if traffic["loop"] == "closed":
            self.requests = []
            self.client_seqs = self._closed_seqs()
        else:
            self.client_seqs = []
            self.requests = self._open_schedule(seconds)

    # -- reads ------------------------------------------------------------
    def read_text(self, name: str, dept: int) -> str:
        d = self.data
        t = self.texts[name]
        u, dl = int(d.dept_uni[dept]), int(d.dept_local[dept])
        return (t.replace("{dept}", uba.dept_iri(dl, u))
                 .replace("{univ}", uba.univ_iri(u)))

    def _check_flags(self, rng, n) -> np.ndarray:
        k = round(n * float(self.traffic.get("check_share", 1.0)))
        return rng.permutation(np.arange(n) < k)

    def _fixed_reads(self, n: int, stream: int):
        """n (query, department) pairs, the same for every seed."""
        fixed = np.random.default_rng([MIX_SEED, stream])
        names = fixed.choice(len(self.names), size=n, p=self.read_p)
        depts = fixed.choice(len(self.dept_p), size=n, p=self.dept_p)
        return names, depts

    def _closed_seqs(self) -> list[list[Request]]:
        """Each client sends its reads in blocks that hold every query of
        the mix by its weight (a whole number), each block in the seed's
        order; constants are the fixed draws, in the seed's order."""
        n = int(self.traffic.get("requests_per_client", 4096))
        block = np.repeat(np.arange(len(self.names)),
                          [int(self.traffic["reads"][k]) for k in self.names])
        out = []
        for c in range(int(self.traffic["clients"])):
            rng = np.random.default_rng([self.seed, 1, c])
            names = np.concatenate([rng.permutation(block)
                                    for _ in range(-(-n // len(block)))])[:n]
            depts = rng.permutation(self._fixed_reads(n, 1 + c)[1])
            check = self._check_flags(rng, n)
            out.append([
                Request("read", self.names[k],
                        self.read_text(self.names[k], int(dp)),
                        check=bool(ch))
                for k, dp, ch in zip(names, depts, check)
            ])
        return out

    # -- writes -----------------------------------------------------------
    def _new_student(self) -> Student:
        d, rng = self.data, self._wrng
        dept = int(rng.choice(len(self.dept_p), p=self.dept_p))
        u, dl = int(d.dept_uni[dept]), int(d.dept_local[dept])
        k = self.next_student.get(dept,
                                  int(d.counts["UndergraduateStudent"][dept]))
        self.next_student[dept] = k + 1
        cls = "UndergraduateStudent"
        iri = uba.entity_iri(dl, u, cls, k)
        t = [(iri, uba.RDF_TYPE, uba.ub(cls)),
             (iri, uba.ub("name"), f'"{cls}{k}"'),
             (iri, uba.ub("emailAddress"), uba.email_literal(dl, u, cls, k)),
             (iri, uba.ub("telephone"), uba.TELEPHONE),
             (iri, uba.ub("memberOf"), uba.dept_iri(dl, u))]
        w = self.traffic["write"]
        n_courses = int(d.counts["Course"][dept])
        n_take = min(n_courses, int(rng.integers(w["courses"][0],
                                                 w["courses"][1] + 1)))
        for ci in sorted(rng.choice(n_courses, n_take, replace=False)):
            t.append((iri, uba.ub("takesCourse"),
                      uba.entity_iri(dl, u, "Course", int(ci))))
        if rng.random() < float(w["advisor_share"]):
            cls_p = uba.PROFESSORS[int(rng.integers(0, 3))]
            n = int(d.counts[cls_p][dept])
            t.append((iri, uba.ub("advisor"),
                      uba.entity_iri(dl, u, cls_p, int(rng.integers(0, n)))))
        self.n_students += 1
        return Student(self.n_students - 1, dept, t)

    def _open_schedule(self, seconds: float) -> list[Request]:
        """Poisson arrivals given their number: rate x seconds requests,
        the gaps a fixed draw of n + 1 exponentials scaled to the window,
        in the seed's order; the writes' places and the reads' order are
        the seed's too."""
        rng = np.random.default_rng([self.seed, 2])
        n = round(float(self.traffic["rate_per_s"]) * seconds)
        fixed = np.random.default_rng([MIX_SEED, 0])
        gaps = rng.permutation(fixed.exponential(1.0, n + 1))
        t = np.cumsum(gaps)[:n] * (seconds / gaps.sum())
        n_w = round(n * float(self.traffic.get("write_share", 0)))
        is_write = rng.permutation(np.arange(n) < n_w)
        order = rng.permutation(n - n_w)
        names, depts = (a[order] for a in self._fixed_reads(n - n_w, 0))
        check = self._check_flags(rng, n - n_w)
        out, live, n_writes = [], list(self.live), 0
        for i in range(n):
            j = i - n_writes
            if is_write[i]:
                if n_writes % 2 == 0 or not live:
                    st = self._new_student()
                    live.append(st)
                    r = Request("write", "insert",
                                st.update_text("INSERT"), student=st)
                else:
                    st = live.pop(0)
                    r = Request("write", "delete",
                                st.update_text("DELETE"), student=st)
                n_writes += 1
            else:
                nm = self.names[names[j]]
                r = Request("read", nm, self.read_text(nm, int(depts[j])),
                            check=bool(check[j]))
            r.t_sched = float(t[i])
            out.append(r)
        return out

    def possible_reads(self) -> list[str]:
        """Every read text the mix can send, whatever the seed: each query
        with each department (or university) it can draw."""
        out: dict[str, None] = {}
        first_of_univ = np.unique(self.data.dept_uni, return_index=True)[1]
        for nm in self.names:
            t = self.texts[nm]
            depts = (range(len(self.dept_p)) if "{dept}" in t
                     else first_of_univ if "{univ}" in t else [0])
            for dp in depts:
                out.setdefault(self.read_text(nm, int(dp)))
        return list(out)

    def all_students(self) -> list[Student]:
        out = [r.student for r in self.setup_writes]
        out += [r.student for r in self.requests
                if r.kind == "write" and r.name == "insert"]
        return out


# -- the loops ----------------------------------------------------------------


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def _do_read(srv, r: Request, deadline: float) -> None:
    r.t_send = time.perf_counter()
    r.t_from = r.t_from or r.t_send
    try:
        with _annotate("query"):
            res = srv.query(r.text, timeout_ms=max(
                1.0, 1e3 * (deadline - r.t_send)))
        r.n_rows = len(res.rows)
        if r.check:
            r.rows = res.rows
        r.ok = True
    except Exception as e:  # a failed request counts in `failed`
        r.error = f"{type(e).__name__}: {e}"[:300]
    r.t_done = time.perf_counter()


def _do_write(srv, r: Request, on_ack=None) -> None:
    r.t_send = time.perf_counter()
    r.t_from = r.t_from or r.t_send
    try:
        with _annotate("update"):
            res = srv.update(r.text)
        r.ack = (res.inserted, res.deleted)
        r.ok = True
    except Exception as e:
        r.error = f"{type(e).__name__}: {e}"[:300]
    r.t_done = time.perf_counter()
    if on_ack is not None:
        on_ack()


def run_closed(srv, sched: Schedule, t_open: float,
               seconds: float) -> list[Request]:
    """Each client sends its next read after its previous answer arrives,
    until the window closes; every request sent is waited for."""
    t_close = t_open + seconds
    deadline = t_close + LATE_S
    sent: list[list[Request]] = [[] for _ in sched.client_seqs]

    def client(c: int) -> None:
        for r in sched.client_seqs[c]:
            if time.perf_counter() >= t_close:
                return
            sent[c].append(r)
            _do_read(srv, r, deadline)

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(len(sched.client_seqs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=max(0.0, deadline + 10 - time.perf_counter()))
    return [r for s in sent for r in s]


def run_open(srv, sched: Schedule, t_open: float, seconds: float,
             workers: int, on_ack=None) -> list[Request]:
    """Sends each request at its scheduled time: reads on a pool of
    worker threads, writes in order on one writer thread. Latency runs
    from the scheduled time, so a late send counts against the server."""
    deadline = t_open + seconds + LATE_S
    reads: queue.Queue = queue.Queue()
    writes: queue.Queue = queue.Queue()

    def reader() -> None:
        while True:
            r = reads.get()
            if r is None:
                return
            _do_read(srv, r, deadline)

    def writer() -> None:
        while True:
            r = writes.get()
            if r is None:
                return
            _do_write(srv, r, on_ack)

    pool = [threading.Thread(target=reader, daemon=True)
            for _ in range(workers)]
    wthread = threading.Thread(target=writer, daemon=True)
    for t in pool + [wthread]:
        t.start()
    sent = []
    for r in sched.requests:
        due = t_open + r.t_sched
        now = time.perf_counter()
        if due > now:
            time.sleep(due - now)
        r.t_from = due
        (writes if r.kind == "write" else reads).put(r)
        sent.append(r)
    for _ in pool:
        reads.put(None)
    writes.put(None)
    for t in pool + [wthread]:
        t.join(timeout=max(0.0, deadline + 10 - time.perf_counter()))
    return sent
