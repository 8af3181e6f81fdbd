"""Records the phase trace the phase-reduction test reads.

    python3 bench/testdata/make_phase_trace.py   # on a machine with the chip

A LUBM(1) store served through SPARQLServer with a tracer: two client
threads send a two-join read (and a one-join read) inside the `window`
annotation the harness also writes, so the trace holds the batcher's and
the decode workers' `mapsq.*` annotations beside the scoped device
programs (host Python calls are not traced, to keep the file small).
Writes bench/testdata/phases.xplane.pb and phases.scopes.json (the
engine's `op_scopes()`, without the instructions no scope names) and
prints the trace's XLA modules beside the executables' keys.
"""
import glob
import json
import os
import shutil
import sys
import tempfile
import threading

import jax

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "..", "..", "src"))
sys.path.insert(0, os.path.join(HERE, ".."))

P = ("PREFIX ub: <http://swat.cse.lehigh.edu/onto/univ-bench.owl#>\n"
     "PREFIX rdf: <http://www.w3.org/1999/02/22-rdf-syntax-ns#>\n")
TWO_JOINS = P + ("SELECT ?x ?y ?z WHERE { ?x ub:memberOf ?z . "
                 "?z ub:subOrganizationOf ?y . "
                 "?x rdf:type ub:GraduateStudent . }")
ONE_JOIN = P + "SELECT ?x ?y WHERE { ?x rdf:type ub:Course . ?x ub:name ?y . }"


def main() -> None:
    from repro.obs.trace import Tracer
    from repro.serve.sparql_server import SPARQLServer
    from repro.sparql import lubm
    from repro.sparql.engine import QueryEngine

    import phases

    if jax.default_backend() != "tpu":
        sys.exit(f"refusing to record on {jax.default_backend()!r}: "
                 "the test reads this trace as the TPU's format")
    engine = QueryEngine(lubm.generate(scale=1), tracer=Tracer())
    srv = SPARQLServer(engine, max_wait_s=0.005)
    try:
        for q in (TWO_JOINS, ONE_JOIN):  # compile solo and stacked shapes
            srv.query(q)
            engine.run_batch([engine.prepare(q)] * 2)

        def client(k: int) -> None:
            for j in range(6):
                srv.query(TWO_JOINS if j < 3 else ONE_JOIN)

        d = tempfile.mkdtemp()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=options)
        with jax.profiler.TraceAnnotation("window"):
            ts = [threading.Thread(target=client, args=(k,))
                  for k in range(2)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
        jax.profiler.stop_trace()
    finally:
        srv.close()
    src = sorted(glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                           recursive=True))[-1]
    dst = os.path.join(HERE, "phases.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(d, ignore_errors=True)
    scopes = engine.op_scopes()
    with open(os.path.join(HERE, "phases.scopes.json"), "w") as f:
        json.dump({k: {i: s for i, s in m.items() if s}
                   for k, m in scopes.items()}, f, sort_keys=True)
    print(f"{dst}: {os.path.getsize(dst)} bytes, device "
          f"{jax.devices()[0].device_kind}")
    print("executables:", sorted(scopes))
    for pname, lines in phases.load(dst):
        for lname, evs in lines:
            if pname.startswith("/device:") and lname == "XLA Modules":
                print("modules:", sorted({e[0] for e in evs}))
            if lname == phases.LAUNCHES:
                print("launched:", sorted({e[0] for e in evs}))
    r = phases.reduce_planes(phases.load(dst), scopes)
    if r is not None:
        print("module keys:", r.module_keys)
        print("idle by phase:", r.idle_s, "scoped", r.scoped_s, "of busy",
              r.busy_s)


if __name__ == "__main__":
    main()
