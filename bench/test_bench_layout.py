"""The store layout a configuration names: the sharded cell runs through
the harness on ShardedQueryEngine over four forced host devices (in a
subprocess, so the parent keeps one device), reads every device's memory,
and comes out not correct with its served path broken; a layout that does
not fit the cell's chips is refused before any data is generated."""
import json
import os
import subprocess
import sys

import pytest

import harness
from test_bench_run import KEYS

HERE = os.path.dirname(os.path.abspath(__file__))
SHARDED = "lubm20-sharded.complex"


@pytest.fixture(scope="module")
def sharded_runs(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "layout_prog.py"),
         str(tmp_path_factory.mktemp("layout_cache"))],
        capture_output=True, text=True, env=env, timeout=240)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sharded_run_is_correct_and_reads_every_device(sharded_runs):
    res = sharded_runs["sound"]
    assert set(res) == KEYS and list(res)[-1] == "checks"
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    dev = res["device"]
    assert dev["count"] == 4
    assert len(dev["memory_peak_bytes_per_device"]) == 4
    assert dev["memory_peak_bytes"] == max(dev["memory_peak_bytes_per_device"])
    want = harness.metric_names(harness.benchmark(), SHARDED, False)
    assert set(res["metrics"]) == set(want) >= {"queries_per_s", "setup_s"}


def test_sharded_run_with_half_the_rows_is_not_correct(sharded_runs):
    res = sharded_runs["half"]
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("workload,layout", [
    ("lubm20.complex", {"partition": "subject_hash"}),  # one chip
    (SHARDED, None),  # four chips, one store
    (SHARDED, {"partition": "round_robin"}),  # no such layout
])
def test_layout_that_does_not_fit_the_cell_is_refused_before_generation(
        workload, layout, monkeypatch, tmp_path):
    def generate(*args, **kw):
        raise AssertionError("data generated before the layout was checked")

    monkeypatch.setattr(harness.uba, "generate", generate)
    with pytest.raises(ValueError, match="layout"):
        harness.run(workload, 1, 1.0, False, 0.0, require_platform=None,
                    config_override={"layout": layout},
                    cache_root=str(tmp_path))


def test_configuration_without_layout_is_one_store_on_one_chip():
    assert harness.store_layout({}, 1) is None
    assert harness.store_layout(
        {"layout": {"partition": "subject_hash"}}, 4) == "subject_hash"
