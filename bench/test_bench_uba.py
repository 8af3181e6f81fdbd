"""The UBA-shaped generator: its ranges, its IRI forms, and that one seed
gives one data set."""
import json
import os

import numpy as np
import pytest

import uba

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(HERE, "configs", "lubm20.json")) as f:
        c = json.load(f)
    return {**c, "universities": 2}


@pytest.fixture(scope="module")
def data(config):
    return uba.generate(config, 2**31 + 11)


def _per_subject(data, pred, subjects):
    t = data.triples
    m = t[:, 1] == data.ids[pred]
    counts = dict(zip(*np.unique(t[m, 0], return_counts=True)))
    return np.array([counts.get(int(s), 0) for s in subjects])


def _typed(data, cls):
    t = data.triples
    return t[(t[:, 1] == data.ids["type"]) & (t[:, 2] == data.ids[cls]), 0]


def test_department_ranges(data, config):
    r = config["ranges"]
    n_dept = np.bincount(data.dept_uni)
    assert len(n_dept) == 2
    assert n_dept.min() >= r["departments"][0]
    assert n_dept.max() <= r["departments"][1]
    keys = {"FullProfessor": "full_professors",
            "AssociateProfessor": "associate_professors",
            "AssistantProfessor": "assistant_professors",
            "Lecturer": "lecturers", "ResearchGroup": "research_groups"}
    for cls, key in keys.items():
        c = data.counts[cls]
        assert r[key][0] <= c.min() and c.max() <= r[key][1], cls
    fac = sum(data.counts[c] for c in uba.FACULTY)
    ug = data.counts["UndergraduateStudent"] / fac
    gs = data.counts["GraduateStudent"] / fac
    assert r["undergraduates_per_faculty"][0] <= ug.min()
    assert ug.max() <= r["undergraduates_per_faculty"][1]
    assert r["graduates_per_faculty"][0] <= gs.min()
    assert gs.max() <= r["graduates_per_faculty"][1]
    for cls, key in (("Course", "courses_per_faculty"),
                     ("GraduateCourse", "graduate_courses_per_faculty")):
        per = data.counts[cls] / fac
        assert r[key][0] <= per.min() and per.max() <= r[key][1]


def test_student_fanouts(data, config):
    r = config["ranges"]
    ug = _typed(data, "UndergraduateStudent")
    gs = _typed(data, "GraduateStudent")
    assert len(ug) == data.counts["UndergraduateStudent"].sum()
    k = _per_subject(data, "takesCourse", ug)
    assert k.min() >= r["courses_per_undergraduate"][0]
    assert k.max() <= r["courses_per_undergraduate"][1]
    k = _per_subject(data, "takesCourse", gs)
    assert k.min() >= r["courses_per_graduate"][0]
    assert k.max() <= r["courses_per_graduate"][1]
    adv = _per_subject(data, "advisor", ug)
    assert adv.max() == 1
    assert abs(adv.mean() - config["undergraduate_advisor_share"]) < 0.02
    assert (_per_subject(data, "advisor", gs) == 1).all()
    assert (_per_subject(data, "undergraduateDegreeFrom", gs) == 1).all()
    assert (_per_subject(data, "undergraduateDegreeFrom", ug) == 0).all()
    for pred in ("name", "emailAddress", "telephone", "memberOf"):
        assert (_per_subject(data, pred, ug) == 1).all(), pred


def test_faculty_facts(data, config):
    full = _typed(data, "FullProfessor")
    pubs = _per_subject  # publications point at their author
    t = data.triples
    m = t[:, 1] == data.ids["publicationAuthor"]
    authored = dict(zip(*np.unique(t[m, 2], return_counts=True)))
    n = np.array([authored.get(int(f), 0) for f in full])
    lo, hi = config["ranges"]["publications"]["FullProfessor"]
    assert lo <= n.min() and n.max() <= hi
    for deg in ("undergraduateDegreeFrom", "mastersDegreeFrom",
                "doctoralDegreeFrom"):
        assert (pubs(data, deg, full) == 1).all()
    heads = t[t[:, 1] == data.ids["headOf"]]
    assert len(heads) == len(data.dept_uni)
    assert np.isin(heads[:, 0], full).all()


def test_iri_and_literal_forms(data):
    terms = set(data.terms)
    assert "<http://www.Department0.University1.edu/UndergraduateStudent12>" in terms
    assert "<http://www.University1.edu>" in terms
    assert '"UndergraduateStudent12@Department0.University1.edu"' in terms
    assert '"UndergraduateStudent12"' in terms
    assert uba.TELEPHONE in terms
    assert ("<http://www.Department0.University1.edu/FullProfessor0/"
            "Publication0>") in terms


def test_no_duplicate_triples_or_terms(data):
    assert len(np.unique(data.triples, axis=0)) == len(data.triples)
    assert len(set(data.terms)) == len(data.terms)


def test_same_seed_same_data(config, data):
    again = uba.generate(config, 2**31 + 11)
    assert again.terms == data.terms
    assert np.array_equal(again.triples, data.triples)
    other = uba.generate(config, 12)
    assert not (len(other.triples) == len(data.triples)
                and np.array_equal(other.triples, data.triples))


def test_every_seed_has_the_same_sizes(config, data):
    """The run's seed draws who relates to whom, not how many: every
    predicate and every class holds as many rows under any seed."""
    other = uba.generate(config, 12)
    assert other.terms == data.terms
    for a, b in ((data, other),):
        for pred in uba.PREDICATES + ("type",):
            ma = a.triples[:, 1] == a.ids[pred]
            mb = b.triples[:, 1] == b.ids[pred]
            assert ma.sum() == mb.sum(), pred
        for cls in uba.CLASSES:
            assert len(_typed(a, cls)) == len(_typed(b, cls)), cls
    assert not np.array_equal(np.sort(other.triples, axis=0),
                              np.sort(data.triples, axis=0))
