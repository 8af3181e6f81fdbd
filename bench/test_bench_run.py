"""Whole runs of both cells at a tiny size on the CPU: the result line
holds the contract's keys, a sound run is correct, and a run whose served
path is broken underneath comes out not correct. The command itself
refuses to run without a TPU."""
import copy
import json
import os
import subprocess
import sys
import time

import pytest

import harness

HERE = os.path.dirname(os.path.abspath(__file__))
KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


@pytest.fixture(scope="module")
def tiny():
    with open(os.path.join(HERE, "configs", "lubm20.json")) as f:
        ranges = json.load(f)["ranges"]
    return {"universities": 1, "degree_universities": 3,
            "ranges": dict(ranges, departments=[2, 3])}


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    """A compile cache of the tests' own; jax's settings come back after."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    yield str(tmp_path_factory.mktemp("bench_cache"))
    for n, v in saved.items():
        jax.config.update(n, v)
    cc.reset_cache()


def _run(workload, tiny, cache, wrapper=None, seed=2**31 + 7, trace=False,
         report=None):
    return harness.run(workload, seed, 4.0, trace, time.perf_counter(),
                       require_platform=None, config_override=tiny,
                       server_wrapper=wrapper, cache_root=cache,
                       report=report)


class Broken:
    """The server with one fault planted where answers or writes are
    produced; everything else passes through."""

    def __init__(self, srv, fault):
        self._srv, self._fault = srv, fault

    def __getattr__(self, name):
        return getattr(self._srv, name)

    def query(self, text, timeout_ms=None):
        res = self._srv.query(text, timeout_ms=timeout_ms)
        rows = [dict(r) for r in res.rows]
        if self._fault == "altered" and rows:
            k = next(iter(rows[0]))
            rows[0][k] = rows[0][k] + "x"
        elif self._fault == "half":
            rows = rows[: len(rows) // 2]
        return type(res)(rows=rows, vars=res.vars, from_cache=res.from_cache)

    def update(self, text):
        if self._fault != "unapplied":
            return self._srv.update(text)
        from repro.sparql.engine import UpdateResult
        from repro.sparql.parser import parse_update

        req = parse_update(text)
        n = sum(len(op.triples) for op in req.ops)
        ins = type(req.ops[0]).__name__ == "InsertData"
        return UpdateResult(n if ins else 0, 0 if ins else n, len(req.ops),
                            self._srv.engine.store.version)


@pytest.mark.parametrize("workload", ["lubm20.complex", "lubm20-live.rw"])
def test_sound_run_is_correct_and_line_holds_contract_keys(
        workload, tiny, cache):
    report = {}
    res = _run(workload, tiny, cache, report=report)
    assert set(res) == KEYS
    assert list(res)[-1] == "checks"
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    bench = harness.benchmark()
    want = {m for m in harness.metric_names(bench, workload, False)}
    assert set(res["metrics"]) == want
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    # set-up knows no text of the window: the window's server parses and
    # plans each distinct text it is sent once
    assert report["prepared_misses"] == report["distinct_reads"] > 0


@pytest.mark.parametrize("workload,fault", [
    ("lubm20.complex", "altered"),
    ("lubm20.complex", "half"),
    ("lubm20-live.rw", "altered"),
    ("lubm20-live.rw", "half"),
    ("lubm20-live.rw", "unapplied"),
])
def test_broken_path_is_not_correct(workload, fault, tiny, cache):
    res = _run(workload, tiny, cache, lambda s: Broken(s, fault))
    assert not res["correct"], (fault, res["checks"])


def test_traced_run_reports_per_layer_metrics(tiny, cache):
    res = _run("lubm20-live.rw", copy.deepcopy(tiny), cache, trace=True)
    assert res["correct"]
    per_layer = {m["name"] for m in harness.benchmark()["per_layer"]}
    assert set(res["metrics"]) <= per_layer
    assert {"window_compiles", "scan_cache_hit_share.rw"} <= set(
        res["metrics"])
    # the window's reads ran the parse and optimize spans
    assert res["metrics"]["prepare_ms_per_query.rw"]["value"] > 0


def test_command_refuses_a_host_without_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         "lubm20.complex", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
