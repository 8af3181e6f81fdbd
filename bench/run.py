"""The benchmark's command: one run of one cell, on the chip.

    python3 bench/run.py --workload lubm20.complex --seed 7 --seconds 30 --trace 0

Builds the cell's store from the seed, warms every shape its seeded
schedule sends, measures for --seconds, compares what was served with the
reference, and prints one JSON object as the last line of standard output
(the numbers compared, each beside its limit, also as the last lines of
standard error). Refuses to run without the TPU chips the cell asks for:
it then prints no result and exits with code 3. --trace 1 records a
profiler trace of the window and reports the cell's per-layer metrics
instead of its end-to-end ones.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # libtpu logs under the run's own TMPDIR, not a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    import harness

    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), T_PROCESS)
    except harness.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    print(f"correct: {result['correct']}", file=sys.stderr)
    for k, v in result["checks"].items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
