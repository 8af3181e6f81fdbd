"""A UBA-shaped LUBM data generator, vectorized in NumPy and seeded.

It follows the Univ-Bench Artificial data generator (UBA 1.7; Guo, Pan &
Heflin, J. Web Semantics 3(2), 2005): its IRI forms
(`http://www.Department3.University7.edu/UndergraduateStudent12`), its
literal forms of name, email and telephone, and the fan-out ranges a
configuration file lists under "ranges". Every range is drawn uniformly,
inclusive at both ends, as UBA draws them.

The sizes of the deployment (departments, people, courses and
publications per department, courses per student, which students have
an advisor or an assistantship) are drawn from the configuration's
`structure_seed`, so every run holds the same numbers of rows in every
pattern and compiles the same programs. The run's seed draws the rest:
which course a student takes, who advises whom, which university grants
a degree, which professor heads a department.

`generate(config, seed)` returns a `Data`: the term list (term id = list
index), the encoded (s, p, o) triples as int32, and the per-department
facts the traffic and the write stream draw from. Nothing here imports
the system under test; the harness hands `terms` and `triples` to the
store, and the reference evaluates queries over the same arrays.
"""
from __future__ import annotations

import dataclasses

import numpy as np

UB = "http://swat.cse.lehigh.edu/onto/univ-bench.owl#"
RDF_TYPE = "<http://www.w3.org/1999/02/22-rdf-syntax-ns#type>"
TELEPHONE = '"xxx-xxx-xxxx"'  # UBA writes this literal for every person

PREDICATES = (
    "name", "emailAddress", "telephone", "memberOf", "worksFor",
    "subOrganizationOf", "undergraduateDegreeFrom", "mastersDegreeFrom",
    "doctoralDegreeFrom", "teacherOf", "takesCourse", "advisor",
    "publicationAuthor", "researchInterest", "headOf",
    "teachingAssistantOf",
)
CLASSES = (
    "University", "Department", "ResearchGroup", "FullProfessor",
    "AssociateProfessor", "AssistantProfessor", "Lecturer",
    "UndergraduateStudent", "GraduateStudent", "Course", "GraduateCourse",
    "Publication", "TeachingAssistant", "ResearchAssistant",
)
FACULTY = ("FullProfessor", "AssociateProfessor", "AssistantProfessor",
           "Lecturer")
PROFESSORS = FACULTY[:3]


def ub(name: str) -> str:
    return f"<{UB}{name}>"


def univ_iri(u: int) -> str:
    return f"<http://www.University{u}.edu>"


def dept_host(d: int, u: int) -> str:
    return f"Department{d}.University{u}.edu"


def dept_iri(d: int, u: int) -> str:
    return f"<http://www.{dept_host(d, u)}>"


def entity_iri(d: int, u: int, cls: str, k: int) -> str:
    return f"<http://www.{dept_host(d, u)}/{cls}{k}>"


def email_literal(d: int, u: int, cls: str, k: int) -> str:
    return f'"{cls}{k}@{dept_host(d, u)}"'


@dataclasses.dataclass
class Data:
    terms: list[str]  # id -> term, in the store's spelling
    triples: np.ndarray  # (n, 3) int32 term ids, no duplicates
    dept_uni: np.ndarray  # (n_dept,) university of each department
    dept_local: np.ndarray  # (n_dept,) department number in its university
    counts: dict[str, np.ndarray]  # per-department entity counts by class
    ids: dict[str, int]  # vocabulary term ids (predicates, classes)

    def term_ids(self) -> dict[str, int]:
        return {t: i for i, t in enumerate(self.terms)}


class _Terms:
    """Append-only term list; blocks of terms get consecutive ids."""

    def __init__(self):
        self.terms: list[str] = []

    def add(self, names: list[str]) -> np.ndarray:
        base = len(self.terms)
        self.terms.extend(names)
        return np.arange(base, base + len(names), dtype=np.int64)

    def one(self, name: str) -> int:
        return int(self.add([name])[0])


def _draw(rng, lo_hi, size) -> np.ndarray:
    lo, hi = lo_hi
    return rng.integers(lo, hi + 1, size=size)


def _local_index(counts: np.ndarray) -> np.ndarray:
    """0..c-1 within each group, for groups of the given sizes."""
    starts = np.repeat(np.cumsum(counts) - counts, counts)
    return np.arange(int(counts.sum())) - starts


def _pick_within(rng, owner_start, owner_count, n_draw) -> np.ndarray:
    """For each draw, a uniform member of its group: group start plus an
    offset below the group's size (owner_* are per draw)."""
    return owner_start + (rng.random(n_draw) * owner_count).astype(np.int64)


def _distinct_picks(rng, group_start, group_size, k_per_item):
    """For each item, k distinct members of its group (k <= group size):
    k uniform draws, a repeat moved to the next free member. Returns
    (item index, member id) pairs."""
    n = len(k_per_item)
    kmax = int(k_per_item.max()) if n else 0
    if n == 0 or kmax == 0:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    offs = (rng.random((n, kmax)) * group_size[:, None]).astype(np.int64)
    for j in range(1, kmax):
        for _ in range(kmax):
            dup = (offs[:, j:j + 1] == offs[:, :j]).any(axis=1)
            if not dup.any():
                break
            offs[dup, j] = (offs[dup, j] + 1) % group_size[dup]
    keep = np.arange(kmax)[None, :] < k_per_item[:, None]
    item = np.broadcast_to(np.arange(n)[:, None], (n, kmax))[keep]
    return item, (group_start[:, None] + offs)[keep]


def generate(config: dict, seed: int) -> Data:
    r = config["ranges"]
    n_univ = int(config["universities"])
    n_degree_univ = int(config["degree_universities"])
    srng = np.random.default_rng(int(config.get("structure_seed", 0)))
    rng = np.random.default_rng(np.uint64(seed))
    T = _Terms()
    ids: dict[str, int] = {"type": T.one(RDF_TYPE)}
    for p in PREDICATES:
        ids[p] = T.one(ub(p))
    for c in CLASSES:
        ids[c] = T.one(ub(c))
    ids["telephone_lit"] = T.one(TELEPHONE)
    interests = T.add([f'"Research{i}"' for i in
                       range(int(config["research_interests"]))])
    univ = T.add([univ_iri(u) for u in range(n_degree_univ)])

    n_dept_u = _draw(srng, r["departments"], n_univ)
    dept_uni = np.repeat(np.arange(n_univ), n_dept_u)
    dept_local = _local_index(n_dept_u)
    n_dept = len(dept_uni)
    du = list(zip(dept_local.tolist(), dept_uni.tolist()))
    dept = T.add([dept_iri(d, u) for d, u in du])
    dept_names = T.add([f'"Department{i}"' for i in range(int(n_dept_u.max()))])

    counts: dict[str, np.ndarray] = {}
    for cls, key in (("FullProfessor", "full_professors"),
                     ("AssociateProfessor", "associate_professors"),
                     ("AssistantProfessor", "assistant_professors"),
                     ("Lecturer", "lecturers")):
        counts[cls] = _draw(srng, r[key], n_dept)
    n_fac = sum(counts[c] for c in FACULTY)
    counts["UndergraduateStudent"] = n_fac * _draw(
        srng, r["undergraduates_per_faculty"], n_dept)
    counts["GraduateStudent"] = n_fac * _draw(
        srng, r["graduates_per_faculty"], n_dept)
    counts["ResearchGroup"] = _draw(srng, r["research_groups"], n_dept)

    blocks: list[np.ndarray] = []

    def emit(s, p, o):
        s = np.asarray(s, np.int64)
        blocks.append(np.stack(np.broadcast_arrays(
            s, np.asarray(p, np.int64), np.asarray(o, np.int64)), axis=1))

    def people(cls: str):
        """IRIs, names and emails of one class in every department;
        returns entity ids, owning department and number within it."""
        c = counts[cls]
        owner = np.repeat(np.arange(n_dept), c)
        local = _local_index(c)
        ol = list(zip(owner.tolist(), local.tolist()))
        ent = T.add([entity_iri(du[o][0], du[o][1], cls, k) for o, k in ol])
        names = T.add([f'"{cls}{k}"' for k in range(int(c.max()))])
        mails = T.add([email_literal(du[o][0], du[o][1], cls, k)
                       for o, k in ol])
        emit(ent, ids["type"], ids[cls])
        emit(ent, ids["name"], names[local])
        emit(ent, ids["emailAddress"], mails)
        emit(ent, ids["telephone"], ids["telephone_lit"])
        return ent, owner, local

    emit(dept, ids["type"], ids["Department"])
    emit(dept, ids["name"], dept_names[dept_local])
    emit(dept, ids["subOrganizationOf"], univ[dept_uni])

    groups_owner = np.repeat(np.arange(n_dept), counts["ResearchGroup"])
    groups_local = _local_index(counts["ResearchGroup"])
    groups = T.add([entity_iri(du[o][0], du[o][1], "ResearchGroup", k)
                    for o, k in zip(groups_owner.tolist(),
                                    groups_local.tolist())])
    emit(groups, ids["type"], ids["ResearchGroup"])
    emit(groups, ids["subOrganizationOf"], dept[groups_owner])

    degree_from = []  # university ids named by any degree
    fac_ids, fac_owner, fac_cls = [], [], []
    for cls in FACULTY:
        ent, owner, local = people(cls)
        emit(ent, ids["worksFor"], dept[owner])
        for deg in ("undergraduateDegreeFrom", "mastersDegreeFrom",
                    "doctoralDegreeFrom"):
            u = rng.integers(0, n_degree_univ, len(ent))
            degree_from.append(u)
            emit(ent, ids[deg], univ[u])
        if cls in PROFESSORS:
            emit(ent, ids["researchInterest"],
                 interests[rng.integers(0, len(interests), len(ent))])
        fac_ids.append(ent)
        fac_owner.append(owner)
        fac_cls.append(np.full(len(ent), FACULTY.index(cls)))
        if cls == "FullProfessor":
            first = np.cumsum(counts[cls]) - counts[cls]
            head = first + (rng.random(n_dept) * counts[cls]).astype(np.int64)
            emit(ent[head], ids["headOf"], dept)
    fac_ids = np.concatenate(fac_ids)
    fac_owner = np.concatenate(fac_owner)
    fac_cls = np.concatenate(fac_cls)
    # faculty sorted by department, so a department's faculty is a range
    order = np.argsort(fac_owner, kind="stable")
    fac_ids, fac_owner, fac_cls = fac_ids[order], fac_owner[order], fac_cls[order]

    # courses: each faculty member teaches 1-2 courses and 1-2 graduate
    # courses, numbered per department in teaching order
    course_range = {}
    for cls, key in (("Course", "courses_per_faculty"),
                     ("GraduateCourse", "graduate_courses_per_faculty")):
        per_fac = _draw(srng, r[key], len(fac_ids))
        teacher = np.repeat(fac_ids, per_fac)
        owner = np.repeat(fac_owner, per_fac)
        c_dept = np.bincount(owner, minlength=n_dept)
        local = _local_index(c_dept)
        courses = T.add([entity_iri(du[o][0], du[o][1], cls, k)
                         for o, k in zip(owner.tolist(), local.tolist())])
        names = T.add([f'"{cls}{k}"' for k in range(int(c_dept.max()))])
        emit(courses, ids["type"], ids[cls])
        emit(courses, ids["name"], names[local])
        emit(teacher, ids["teacherOf"], courses)
        counts[cls] = c_dept
        course_range[cls] = (courses[0] + np.cumsum(c_dept) - c_dept, c_dept)

    # professors of a department (advisors), as ranges over a sorted array
    prof_ids = fac_ids[fac_cls < 3]
    prof_owner = fac_owner[fac_cls < 3]
    n_prof = np.bincount(prof_owner, minlength=n_dept)
    prof_start = np.cumsum(n_prof) - n_prof

    def advise(students, owner):
        pick = _pick_within(rng, prof_start[owner], n_prof[owner],
                            len(students))
        emit(students, ids["advisor"], prof_ids[pick])

    def take(students, owner, cls, key):
        start, size = course_range[cls]
        k = np.minimum(_draw(srng, r[key], len(students)), size[owner])
        item, course = _distinct_picks(rng, start[owner], size[owner], k)
        emit(students[item], ids["takesCourse"], course)

    ug, ug_owner, _ = people("UndergraduateStudent")
    emit(ug, ids["memberOf"], dept[ug_owner])
    take(ug, ug_owner, "Course", "courses_per_undergraduate")
    with_adv = srng.random(len(ug)) < float(config["undergraduate_advisor_share"])
    advise(ug[with_adv], ug_owner[with_adv])

    gs, gs_owner, _ = people("GraduateStudent")
    emit(gs, ids["memberOf"], dept[gs_owner])
    u = rng.integers(0, n_degree_univ, len(gs))
    degree_from.append(u)
    emit(gs, ids["undergraduateDegreeFrom"], univ[u])
    take(gs, gs_owner, "GraduateCourse", "courses_per_graduate")
    advise(gs, gs_owner)
    ta_share = srng.uniform(*config["teaching_assistant_share"], n_dept)
    ta = srng.random(len(gs)) < ta_share[gs_owner]
    start, size = course_range["Course"]
    emit(gs[ta], ids["type"], ids["TeachingAssistant"])
    emit(gs[ta], ids["teachingAssistantOf"],
         _pick_within(rng, start[gs_owner[ta]], size[gs_owner[ta]],
                      int(ta.sum())))
    ra_share = srng.uniform(*config["research_assistant_share"], n_dept)
    ra = srng.random(len(gs)) < ra_share[gs_owner]
    emit(gs[ra], ids["type"], ids["ResearchAssistant"])

    # publications, by the author's rank; graduate students co-author
    # publications of their own department's faculty
    pubs_key = r["publications"]
    n_pub = np.zeros(len(fac_ids), np.int64)
    for i, cls in enumerate(FACULTY):
        m = fac_cls == i
        n_pub[m] = _draw(srng, pubs_key[cls], int(m.sum()))
    author = np.repeat(fac_ids, n_pub)
    author_owner = np.repeat(fac_owner, n_pub)
    pub_local = _local_index(n_pub)
    author_term = [T.terms[a][:-1] for a in author.tolist()]
    pubs = T.add([f"{a}/Publication{k}>" for a, k in
                  zip(author_term, pub_local.tolist())])
    pub_names = T.add([f'"Publication{k}"' for k in range(int(n_pub.max()))])
    emit(pubs, ids["type"], ids["Publication"])
    emit(pubs, ids["name"], pub_names[pub_local])
    emit(pubs, ids["publicationAuthor"], author)
    order = np.argsort(author_owner, kind="stable")
    pubs_by_dept = pubs[order]
    n_pub_dept = np.bincount(author_owner, minlength=n_dept)
    pub_start = np.cumsum(n_pub_dept) - n_pub_dept
    k = np.minimum(_draw(srng, r["graduate_publications"], len(gs)),
                   n_pub_dept[gs_owner])
    item, pub_pos = _distinct_picks(rng, pub_start[gs_owner],
                                    n_pub_dept[gs_owner], k)
    emit(pubs_by_dept[pub_pos], ids["publicationAuthor"], gs[item])

    # universities: the generated ones and every one a degree names
    named = np.unique(np.concatenate(degree_from + [np.arange(n_univ)]))
    emit(univ[named], ids["type"], ids["University"])

    triples = np.concatenate(blocks).astype(np.int32)
    return Data(
        terms=T.terms,
        triples=triples,
        dept_uni=dept_uni,
        dept_local=dept_local,
        counts=counts,
        ids=ids,
    )
