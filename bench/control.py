"""The control: the reference put in the program's place, with term ids
compared at 16 bits instead of 21, fed through the same comparison that
decides a run's `correct`. It has to come out as not correct.

    python3 bench/control.py --workload lubm20.complex --seed 5
    python3 bench/control.py --workload lubm20.complex --seed 5 --key-bits 21

With --key-bits 21 the stand-in is the exact reference, which has to read
0 on every number. A closed-loop cell answers the first
--requests-per-client reads of each client; an open-loop cell answers
every request its schedule sends in --seconds, each read at the store
state left by the writes before it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

import check
import harness
import loadgen
import reference
import uba


def control_log(workload: str, seed: int, seconds: float, per_client: int,
                key_bits: int, config_override: dict | None = None):
    bench = harness.benchmark()
    _, config, traffic = harness.cell(bench, workload)
    if config_override:
        config = {**config, **config_override}
    data = uba.generate(config, seed)
    sched = loadgen.Schedule(traffic, data, seed, seconds,
                             int(config.get("store", {})
                                 .get("live_inserted", 0)))
    model = check.Model(data, sched.all_students())
    stand_in = check.Model(data, sched.all_students(), key_bits=key_bits)
    if sched.client_seqs:
        reqs = [r for seq in sched.client_seqs for r in seq[:per_client]]
    else:
        reqs = list(sched.requests)
    for r in sched.setup_writes:
        r.ok, r.ack = True, (len(r.student.triples), 0)
    live = check.states([], len(sched.setup_writes),
                        len(model.students))[0]
    t = 1.0
    for r in reqs:
        r.t_from = r.t_send = t
        r.t_done = t + 0.5
        t += 1.0
        r.ok = True
        if r.kind == "write":
            n = len(r.student.triples)
            ins = r.name == "insert"
            r.ack = (n, 0) if ins else (0, n)
            live[r.student.index] = ins
            continue
        select, vars_, rows, stud = stand_in.bindings(r.text)
        if rows.size and stand_in.students:
            rows = rows[np.all((stud < 0) | live[np.clip(stud, 0, None)],
                               axis=1)]
        proj = reference.project(select, (vars_, rows))
        terms = stand_in.terms
        r.rows = [{v: terms[i] for v, i in zip(select, row)}
                  for row in proj.tolist()] if r.check else None
        r.n_rows = len(proj)
    return model, reqs, sched.setup_writes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--requests-per-client", type=int, default=64)
    ap.add_argument("--key-bits", type=int, default=16)
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    model, reqs, setup = control_log(args.workload, args.seed, args.seconds,
                                     args.requests_per_client, args.key_bits)
    readings = check.check(model, reqs, setup)
    out = {k: v for k, (v, _) in readings.items()}
    correct = all(v <= lim for v, lim in readings.values())
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "key_bits": args.key_bits, "requests": len(reqs),
                      "correct": correct, "readings": out,
                      "seconds": round(time.perf_counter() - t0, 3)}))
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    sys.exit(main())
