"""Where a traced window's time goes: the device's idle time by serving
phase, its busy time by plan operator, and each read's latency by span.

    python3 bench/phase_report.py --workload lubm20.complex --seed 7 \\
        --seconds 45 [--out phases.json]

Runs the cell as `bench/run.py --trace 1` does (bench/harness.py, on the
chip), and reduces the same profiler trace a second time with
bench/phases.py, given the engine's `op_scopes()`. From the engine's
spans it gives the mean per answered read of each span, beside the mean
read latency the spans should add up to. Prints one JSON object as the
last line (and writes it to --out). The budget's `covers_read` is false,
and a warning goes to stderr, where the spans add up to less than
COVERS of the mean read. A program without phase annotations, scopes or
the newer spans reads None or 0 where those would be. Exits 1 if the
traced run's trace was never reduced here.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BUDGET = ("queue_wait", "prepare", "batch_wait", "stage", "compile",
          "dispatch", "decode_wait", "transfer", "decode")
COVERS = 0.9  # share of the mean read the spans should add up to


class _Window:
    """The window's server: notes how many traces the tracer had finished
    when the first window read arrived (set-up's own reads come before)."""

    def __init__(self, srv, held: dict):
        self._srv, self._held = srv, held
        held["engine"] = srv.engine

    def __getattr__(self, name):
        return getattr(self._srv, name)

    def query(self, text, timeout_ms=None):
        tracer = self._srv.engine.tracer
        self._held.setdefault("n0", tracer.n_traces)
        return self._srv.query(text, timeout_ms=timeout_ms)


def budget(traces) -> dict:
    """Mean ms per answered read of each span name, and of the read."""
    reads = [t for t in traces if t.root.name == "query"
             and t.root.attrs.get("outcome") == "ok"]
    names = sorted({s.name for t in reads for s in t.spans} - {"query"})
    out = {n: 1e3 * sum(s.duration_s for t in reads for s in t.find(n))
           / max(1, len(reads)) for n in names}
    read_ms = 1e3 * sum(t.duration_s for t in reads) / max(1, len(reads))
    sum_ms = sum(out.get(n, 0.0) for n in BUDGET)
    return {
        "reads": len(reads),
        "read_ms": read_ms,
        "spans_ms": out,
        "sum_ms": sum_ms,
        "covers_read": bool(reads) and sum_ms >= COVERS * read_ms,
        "reads_missing": {n: sum(1 for t in reads if not t.find(n))
                          for n in BUDGET},
        "open_spans": sum(len(t.open_spans()) for t in traces),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    import harness
    import phases
    import xplane

    held: dict = {}
    reduce_file = xplane.reduce_file

    def reduce_twice(path, top=10):
        planes = phases.load(path)
        op_scopes = getattr(held["engine"], "op_scopes", None)
        scopes = op_scopes() if op_scopes else None
        held["phases"] = phases.reduce_planes(planes, scopes)
        return xplane.reduce_planes(planes, top)

    xplane.reduce_file = reduce_twice
    report: dict = {}
    try:
        result = harness.run(args.workload, args.seed, args.seconds, True,
                             T_PROCESS,
                             server_wrapper=lambda s: _Window(s, held),
                             report=report)
    except harness.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    finally:
        xplane.reduce_file = reduce_file
    if "phases" not in held:
        print("no result: the harness reduced the trace without "
              "xplane.reduce_file, so the phases were never read",
              file=sys.stderr)
        return 1
    tracer = held["engine"].tracer
    k = tracer.n_traces - held.get("n0", tracer.n_traces)
    traces = tracer.recent()[-k:] if k else []
    ph = held.get("phases")
    out = {
        "workload": args.workload, "seed": args.seed,
        "correct": result["correct"], "failed": result["failed"],
        "answered_per_s": report.get("answered_per_s"),
        "metrics": {k: v["value"] for k, v in result["metrics"].items()},
        "device": result["device"],
        "breakdown": result.get("breakdown"),
        "phases": dataclasses.asdict(ph) if ph is not None else None,
        "budget": budget(traces),
    }
    if not out["budget"]["covers_read"]:
        print(f"warning: the spans add up to {out['budget']['sum_ms']:.1f} "
              f"of {out['budget']['read_ms']:.1f} ms a read, under "
              f"{COVERS:.0%}", file=sys.stderr)
    line = json.dumps(out)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
