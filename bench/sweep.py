"""Finds the knee of an open-loop cell: the highest offered rate it
sustains, each rate a run of its own in one process.

    python3 bench/sweep.py --workload lubm20-live.rw --seed 100 \
        --seconds 30 --rates 4 6 8 10 12

A rate is sustained when every request is answered and the read median
stays under --p50-limit-ms, so no backlog builds. Answers that arrive
just after the window's close are not a backlog (the tail of the last
second's reads); the tail is left to compaction's stalls, which come at
any rate. The cell's traffic file then records 0.8 x the knee, the
highest rate sustained with every lower rate sustained too.
"""
import argparse
import json
import sys
import time

import harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--p50-limit-ms", type=float, default=1000)
    args = ap.parse_args(argv)
    knee = None
    for i, rate in enumerate(sorted(args.rates)):
        report: dict = {}
        res = harness.run(args.workload, args.seed + i, args.seconds, False,
                          time.perf_counter(),
                          traffic_override={"rate_per_s": rate},
                          report=report)
        m = {k: v["value"] for k, v in res["metrics"].items()}
        ok = (res["failed"] == 0
              and m.get("read_p50_ms", 1e9) < args.p50_limit_ms)
        if ok and knee == (sorted(args.rates)[i - 1] if i else None):
            knee = rate
        print(json.dumps({"rate_per_s": rate, "sustained": ok,
                          "correct": res["correct"], **report, **m}),
              flush=True)
    print(json.dumps({"knee_per_s": knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
