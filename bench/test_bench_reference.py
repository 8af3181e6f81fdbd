"""The benchmark's reference agrees with the repository's oracle
(`sparql.baseline.reference_rows`) on L1-L7, and its control (ids
compared at 16 bits) fails the comparison that decides `correct`."""
import json
import os

import numpy as np
import pytest

import check
import control
import loadgen
import reference
import uba

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def tiny():
    with open(os.path.join(HERE, "configs", "lubm20.json")) as f:
        c = json.load(f)
    ranges = dict(c["ranges"], departments=[2, 3])
    return {**c, "universities": 1, "degree_universities": 3,
            "ranges": ranges}


def _program_rows(data, text):
    from repro.sparql.baseline import reference_rows
    from repro.sparql.dictionary import TermDict
    from repro.sparql.parser import parse
    from repro.sparql.store import TripleStore

    d = TermDict()
    d.encode_many(data.terms)
    return reference_rows(TripleStore(data.triples, d), parse(text))


@pytest.mark.parametrize("name", ["L1", "L2", "L3", "L4", "L5", "L6", "L7"])
def test_reference_equals_baseline_oracle(tiny, name):
    data = uba.generate(tiny, 3)
    # every undergraduate degree from the own university: L3 then matches
    t = data.triples
    ug = t[(t[:, 1] == data.ids["type"])
           & (t[:, 2] == data.ids["UndergraduateStudent"]), 0]
    univ0 = data.terms.index(uba.univ_iri(0))
    extra = np.stack([ug[:40], np.full(40, data.ids["undergraduateDegreeFrom"]),
                      np.full(40, univ0)], axis=1).astype(np.int32)
    data.triples = np.concatenate([t, extra])
    sched = loadgen.Schedule({"loop": "closed", "clients": 1,
                              "reads": {name: 1},
                              "requests_per_client": 1}, data, 1, 1.0)
    text = sched.read_text(name, 1)
    model = check.Model(data, [])
    select, bind = reference.evaluate(model.graph, text, model.term_id)
    got = reference.canonical(reference.project(select, bind))
    want = model.encode_rows(select, _program_rows(data, text))
    assert len(got) > 0 or name == "L1"
    assert np.array_equal(got, want)


def test_control_fails_and_reference_passes():
    over = {"universities": 2}
    for workload in ("lubm20.complex", "lubm20-live.rw"):
        for bits, ok in ((16, False), (21, True)):
            model, reqs, setup = control.control_log(
                workload, 7, 3.0, 4, bits, over)
            readings = check.check(model, reqs, setup)
            correct = all(v <= lim for v, lim in readings.values())
            assert correct is ok, (workload, bits, readings)
