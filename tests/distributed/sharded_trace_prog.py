"""Subprocess body: the serving spans on the sharded engine over N forced
host devices (the parent pytest process keeps 1 device).

Reads through SPARQLServer (cold, then warm solo) and a stacked
lanes-x-shards batch through run_batch_pipelined must each carry the
request's spans (stage with its lock wait, dispatch, decode_wait,
transfer, decode; queue_wait and prepare through the server), leave no
span open, and name join scopes in the sharded executables.

Usage: sharded_trace_prog.py [n_devices]   (default 4)
"""
import os
import sys

N_DEV = int(sys.argv[1]) if len(sys.argv) > 1 else 4
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={N_DEV} "
    + os.environ.get("XLA_FLAGS", "")
)

import jax  # noqa: E402

from repro.core import dist_executor as dx  # noqa: E402
from repro.obs import Tracer  # noqa: E402
from repro.serve.sparql_server import SPARQLServer  # noqa: E402
from repro.sparql import lubm  # noqa: E402
from repro.sparql.engine import PendingDecode, ShardedQueryEngine  # noqa: E402
from repro.sparql.sharded_store import shard_store  # noqa: E402

SERVED = ("queue_wait", "prepare", "batch_wait", "stage", "dispatch",
          "decode_wait", "transfer", "decode")


def main() -> None:
    assert jax.device_count() == N_DEV, jax.devices()
    tracer = Tracer()
    eng = ShardedQueryEngine(
        shard_store(lubm.generate(scale=1), N_DEV), tracer=tracer
    )
    text = lubm.QUERIES["Q2"]
    srv = SPARQLServer(eng)
    try:
        srv.query(text)  # cold: calibration, compile, mesh dispatch
        srv.query(text)  # warm solo
    finally:
        srv.close()
    served = srv.recent_traces()
    assert len(served) == 2, served
    for t in served:
        missing = [n for n in SERVED if not t.find(n)]
        assert not missing, (missing, t.tree_str())
        assert "lock_wait_ms" in t.find("stage")[0].attrs
    assert served[0].find("compile"), served[0].tree_str()

    ps = [eng.prepare(text) for _ in range(3)]
    traces = [tracer.new_trace("query") for _ in ps]
    for oc in eng.run_batch_pipelined(ps, traces=traces):
        assert isinstance(oc, PendingDecode), oc
        oc.resolve()
    for t in traces:
        tracer.finish(t)
        for n in ("batch_wait", "stage", "dispatch", "decode_wait",
                  "transfer", "decode"):
            assert t.find(n), (n, t.tree_str())
        assert t.find("stage")[0].attrs["stacked"], t.tree_str()
    ids = {t.find("stage")[0].attrs["dispatch_id"] for t in traces}
    assert len(ids) == 1, ids
    assert tracer.open_span_count() == 0

    scopes = {s for m in eng.op_scopes().values() for s in m.values()}
    assert "join0/sort" in scopes, sorted(scopes)
    moved = sum(
        cnt["emitted"] + cnt["broadcast"]
        for e in eng.plan_cache.entries()
        for cnt in [dx.strategy_counts(e.compiled.strategies)]
    )
    # data movement between shards is named inside its join's scope
    assert moved == 0 or any(s.endswith("/shuffle") for s in scopes), (
        moved, sorted(scopes))
    print(f"SHARDED TRACE SPANS OK n_dev={N_DEV}")


if __name__ == "__main__":
    main()
