"""The seeded schedule: the same seed gives the same requests; writes
alternate insert and delete of the oldest live undergraduate."""
import json
import os

import numpy as np
import pytest

import loadgen
import uba

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def data():
    with open(os.path.join(HERE, "configs", "lubm20.json")) as f:
        c = json.load(f)
    return uba.generate({**c, "universities": 2}, 5)


def _traffic(name):
    return loadgen.load_json("traffic", name + ".json")


def _key(s):
    reqs = s.requests or [r for q in s.client_seqs for r in q]
    return [(r.kind, r.name, r.text, r.t_sched, r.check) for r in reqs]


@pytest.mark.parametrize("mix", ["complex", "rw"])
def test_same_seed_same_schedule(data, mix):
    live = 4 if mix == "rw" else 0
    a = loadgen.Schedule(_traffic(mix), data, 2**31 + 3, 10.0, live)
    b = loadgen.Schedule(_traffic(mix), data, 2**31 + 3, 10.0, live)
    c = loadgen.Schedule(_traffic(mix), data, 9, 10.0, live)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)


def test_open_loop_mix(data):
    t = _traffic("rw")
    s = loadgen.Schedule(t, data, 1, 60.0, 4)
    reqs = s.requests
    assert abs(len(reqs) / 60.0 - t["rate_per_s"]) < 0.15 * t["rate_per_s"]
    writes = [r for r in reqs if r.kind == "write"]
    assert abs(len(writes) / len(reqs) - t["write_share"]) < 0.03
    assert [w.name for w in writes[:4]] == ["insert", "delete"] * 2
    live = [st.index for st in s.live]
    for w in writes:
        if w.name == "insert":
            live.append(w.student.index)
        else:
            assert w.student.index == live.pop(0)
    assert len(live) in (4, 5)
    reads = {r.name for r in reqs if r.kind == "read"}
    assert reads == set(t["reads"])
    assert all(r.t_sched < 60.0 for r in reqs)


def test_zipf_constants_rank_by_department_number(data):
    s = loadgen.Schedule(_traffic("rw"), data, 2, 60.0, 0)
    dept0 = uba.dept_iri(0, 0)
    n0 = sum(dept0 in r.text for r in s.requests if r.name == "L4")
    n_l4 = sum(r.name == "L4" for r in s.requests)
    assert n0 / n_l4 == pytest.approx(s.dept_p[0], abs=0.06)
    assert s.dept_p[0] > s.dept_p[-1] * 20


@pytest.mark.parametrize("mix", ["complex", "rw"])
def test_every_seed_sends_the_same_work(data, mix):
    """Seeds change the order of what is sent, not what: the same texts
    as often, the same number of writes, the same arrival gaps."""
    live = 4 if mix == "rw" else 0
    a = loadgen.Schedule(_traffic(mix), data, 2**31 + 3, 10.0, live)
    b = loadgen.Schedule(_traffic(mix), data, 17, 10.0, live)

    def work(s):
        reqs = s.requests or [r for q in s.client_seqs for r in q[:8]]
        gaps = np.diff([0.0] + [r.t_sched for r in s.requests] + [10.0])
        return (sorted(r.text for r in reqs if r.kind == "read"),
                sorted(r.name for r in reqs if r.kind == "write"),
                np.sort(gaps).round(9).tolist())

    assert work(a) == work(b)
    assert _key(a) != _key(b)
