"""Serving launcher: `python -m repro.launch.serve --mode sparql|lm`.

sparql — stand up the MapSQ engine + micro-batching server over LUBM data
         and run the 5 benchmark queries through it.
lm     — reduced-config LM generation (prefill + greedy decode loop).
"""
from __future__ import annotations

import argparse

import jax
import numpy as np
from repro.core import compat


def serve_sparql(scale: int, n_queries: int, shards: int = 0) -> None:
    """`shards > 0` opens the store SHARDED: subject-hash partitioned over
    a `shards`-device mesh, queries served by the distributed executor
    (one shard_map dispatch per warm query). On the CPU, force host
    devices first: XLA_FLAGS=--xla_force_host_platform_device_count=4."""
    from repro.serve.sparql_server import SPARQLServer
    from repro.sparql.engine import QueryEngine, ShardedQueryEngine
    from repro.sparql.lubm import QUERIES, generate

    store = generate(scale=scale)
    print(f"LUBM-ish store: {len(store)} triples")
    if shards > 0:
        from repro.sparql.sharded_store import shard_store

        sharded = shard_store(store, shards)
        print(f"sharded over {shards} device(s): "
              f"per-shard triples {sharded.shard_sizes()}")
        engine: QueryEngine = ShardedQueryEngine(sharded)
    else:
        engine = QueryEngine(store)
    srv = SPARQLServer(engine)
    import threading

    results = {}

    def ask(name, text):
        results[name] = srv.query(text)

    threads = [
        threading.Thread(target=ask, args=(f"{name}#{i}", text))
        for i in range(n_queries)
        for name, text in QUERIES.items()
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for name in sorted(results):
        print(f"{name}: {len(results[name])} rows")
    print("server stats:", srv.stats())
    srv.close()


def serve_lm(arch: str) -> None:
    import importlib

    from repro.configs.registry import ARCHS
    from repro.launch.mesh import make_local_mesh
    from repro.launch.train import reduced_lm
    from repro.models import transformer as T
    from repro.serve.decode import Generator

    cfg = reduced_lm(importlib.import_module(ARCHS[arch]).CONFIG)
    mesh = make_local_mesh(model=jax.device_count())
    params = T.init_params(jax.random.PRNGKey(0), cfg,
                           ep=mesh.shape["model"])
    gen = Generator(cfg, params, mesh, max_len=64)
    with compat.set_mesh(mesh):
        prompts = np.arange(8, dtype=np.int32).reshape(2, 4) % cfg.vocab
        out = gen.generate(prompts, n_new=16)
    print("generated:", out.shape)
    print(out)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["sparql", "lm"], default="sparql")
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--scale", type=int, default=2)
    ap.add_argument("--n-queries", type=int, default=4)
    ap.add_argument("--shards", type=int, default=0,
                    help="open the store sharded over this many devices "
                         "(0 = single-device store)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.mode == "sparql":
        serve_sparql(args.scale, args.n_queries, args.shards)
    else:
        serve_lm(args.arch)


if __name__ == "__main__":
    main()
