"""One run of one benchmark cell: build, warm up, measure, check, report.

The cell's name is looked up in BENCHMARK.json; its configuration file,
its traffic file and each metric's reader (bench/metrics/<name>.py) are
found by name, so a new cell, mix or metric is new files and entries.
A configuration's `layout` names the store layout: without it one
TripleStore serves from one chip; `{"partition": "subject_hash"}` shards
it over every chip of the cell under one ShardedQueryEngine.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import glob
import importlib.util
import json
import os
import shutil
import statistics
import sys
import tempfile
import threading
import time

import numpy as np

import check
import loadgen
import reference
import uba
from compile_meter import CompileMeter

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE = os.path.join(ROOT, ".bench_cache")


class NoChip(RuntimeError):
    pass


def benchmark() -> dict:
    """BENCHMARK.json, with the cells held out of it (bench/held/*.json,
    each saying why) appended, so that a held cell still runs by name."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for path in sorted(glob.glob(os.path.join(HERE, "held", "*.json"))):
        with open(path) as f:
            held = json.load(f)
        for key in ("configs", "workloads", "end_to_end", "per_layer"):
            bench[key] = bench[key] + held.get(key, [])
    return bench


def cell(bench: dict, workload: str):
    w = {c["name"]: c for c in bench["workloads"]}[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    traffic = loadgen.load_json("traffic", w["traffic"] + ".json")
    return w, config, traffic


def metric_names(bench: dict, workload: str, per_layer: bool) -> list[str]:
    e2e = bench["end_to_end"]
    mine = [m["name"] for m in e2e
            if workload in m.get("workloads", [workload])]
    if not per_layer:
        return mine
    return [m["name"] for m in bench["per_layer"]
            if workload in m.get("workloads", [])
            or ("workloads" not in m and m["moves"] in mine)]


def read_metric(name: str, ctx):
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("metric_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def enable_compile_cache(root: str) -> str:
    import jax

    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(root, "jax")
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return d


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Ctx:
    """What metric readers read."""

    setup_s: float
    log: list
    t_open: float
    t_close: float
    stats0: dict
    stats1: dict
    compiles_in_window: int
    compactions: list  # (start, seconds) of each compaction in the window
    traces: list  # tracer traces finished in the window (traced runs)
    device: object  # xplane.Reduced, traced runs
    device_kind: str
    least_bytes: dict  # read text -> least bytes a device must move
    peaks: dict
    phases: object = None  # phases.Phases, traced runs


LAYOUTS = ("subject_hash",)


def store_layout(config: dict, chips: int) -> str | None:
    """The store layout a configuration names (`"layout": {"partition":
    ...}`), checked against the cell's chips: None is one TripleStore on
    one chip; "subject_hash" shards the store by subject over every chip
    of the cell. A layout that would leave a chip of the cell idle, or
    shard over one chip, is refused."""
    part = (config.get("layout") or {}).get("partition")
    if part is not None and part not in LAYOUTS:
        raise ValueError(f"unknown store layout {part!r}; known: {LAYOUTS}")
    if part is None and chips != 1:
        raise ValueError(f"a cell of {chips} chips names no store layout: "
                         f"one TripleStore would leave {chips - 1} idle")
    if part is not None and chips < 2:
        raise ValueError(f"the {part} layout needs a cell of more than one "
                         f"chip, not {chips}")
    return part


def _signature(model: check.Model, text: str) -> tuple:
    """Reads whose constant patterns fall in the same pow-2 row buckets
    share one compiled shape; one warm-up per signature and width."""
    _, patterns = reference.parse_bgp(text)
    sizes = []
    for p in patterns:
        if not (p[0].startswith("?") and p[2].startswith("?")):
            n = len(model.graph.scan(p, model.term_id)[1])
            sizes.append(1 << max(0, (max(1, n) - 1).bit_length()))
    body = text
    for p in patterns:
        for t in (p[0], p[2]):
            if not t.startswith("?"):
                body = body.replace(t, "")
    return (body, tuple(sizes))


def warm_up(engine, srv, texts: list[str], model: check.Model,
            widths: list[int]) -> None:
    """Compile every shape the mix can send, at every stacked width it
    can reach. Every text the mix can send runs once alone, so each
    shape's join buckets fit its largest member before the window opens;
    then one text of each shape goes through `srv`, a server of set-up's
    own. Shapes compile in parallel: XLA compiles release the interpreter
    lock."""
    groups: dict[tuple, list[str]] = {}
    for t in texts:
        groups.setdefault(_signature(model, t), []).append(t)
    handles = {t: engine.prepare(t) for t in texts}

    def solo(group):
        for t in group:
            handles[t].run()

    def batch(lanes):
        engine.run_batch([handles[t] for t in lanes])

    t0 = time.perf_counter()
    with concurrent.futures.ThreadPoolExecutor(8) as pool:
        for f in [pool.submit(solo, g) for g in groups.values()]:
            f.result()
        log(f"warm-up: {len(texts)} texts in {len(groups)} shapes, solo "
            f"runs {time.perf_counter() - t0:.3f} s")
        jobs = []
        for g in groups.values():
            for w in widths:
                jobs.append([g[0]] * w)
                if len(g) > 1:
                    jobs.append([g[i % len(g)] for i in range(w)])
        by_template: dict[str, list[str]] = {}
        for (body, _), g in groups.items():
            by_template.setdefault(body, []).append(g[0])
        for reps in by_template.values():
            if len(reps) > 1:
                for w in widths:
                    jobs.append([reps[i % len(reps)] for i in range(w)])
        for f in [pool.submit(batch, lanes) for lanes in jobs]:
            f.result()
        log(f"warm-up: {len(jobs)} stacked batches at widths {widths}, "
            f"{time.perf_counter() - t0:.3f} s")
    for g in groups.values():
        srv.query(g[0], timeout_ms=loadgen.LATE_S * 1e3)
    log(f"warm-up: done in {time.perf_counter() - t0:.3f} s")


def least_bytes(model: check.Model, text: str) -> int:
    """The bytes any plan must move for one answer: each pattern's
    matching rows read once (4 bytes per variable column) and the result
    rows written once."""
    select, patterns = reference.parse_bgp(text)
    n = 0
    for p in patterns:
        vars_, rows = model.graph.scan(p, model.term_id)
        n += 4 * rows.size
    rows = model.bindings(text)[2]
    return n + 4 * len(rows) * len(select)


class Compactor:
    """The operator's policy: compact whenever the delta tail holds
    `tail_rows` rows or more, on a thread of its own."""

    def __init__(self, store, tail_rows: int):
        self.store = store
        self.tail_rows = tail_rows
        self.wake = threading.Event()
        self.stop = False
        self.done: list[tuple[float, float]] = []
        self.thread = threading.Thread(target=self._loop, daemon=True)
        self.thread.start()

    def note_write(self) -> None:
        if self.store.write_stats()["tail_rows"] >= self.tail_rows:
            self.wake.set()

    def _loop(self) -> None:
        import jax

        while not self.stop:
            if not self.wake.wait(0.05):
                continue
            self.wake.clear()
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("compact"):
                self.store.compact()
            self.done.append((t0, time.perf_counter() - t0))

    def close(self) -> None:
        self.stop = True
        self.thread.join(timeout=120)


def run(workload: str, seed: int, seconds: float, trace: bool,
        t_process: float, *, require_platform: str | None = "tpu",
        config_override: dict | None = None, server_wrapper=None,
        cache_root: str = CACHE, traffic_override: dict | None = None,
        report: dict | None = None) -> dict:
    bench = benchmark()
    w, config, traffic = cell(bench, workload)
    if config_override:
        config = {**config, **config_override}
    if traffic_override:
        traffic = {**traffic, **traffic_override}
    chips = int(w["chips"])
    partition = store_layout(config, chips)
    seed = int(seed) % (1 << 63)
    os.makedirs(cache_root, exist_ok=True)
    cache_dir = enable_compile_cache(cache_root)
    import jax

    devices = jax.devices()
    dev = devices[0]
    log(f"jax {jax.__version__}: platform={dev.platform} "
        f"device_kind={dev.device_kind} devices={len(devices)}")
    if ((require_platform and dev.platform != require_platform)
            or len(devices) < chips):
        raise NoChip(f"cell {workload} needs {chips} {require_platform} "
                     f"chip(s); jax found {len(devices)} {dev.platform}")
    cell_devices = devices[:chips]
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.obs.trace import Tracer
    from repro.serve.sparql_server import SPARQLServer
    from repro.sparql.dictionary import TermDict
    from repro.sparql.engine import QueryEngine
    from repro.sparql.store import TripleStore

    meter = CompileMeter()
    t0 = time.perf_counter()
    data = uba.generate(config, seed)
    d = TermDict()
    if d.encode_many(data.terms) != list(range(len(data.terms))):
        raise RuntimeError("the term list holds a duplicate")
    store = TripleStore(data.triples, d)
    store.statistics  # the catalog of this data, not a warm-up file's
    t_store = time.perf_counter() - t0
    log(f"store: {len(data.triples)} triples, {len(d)} terms, built in "
        f"{t_store:.3f} s (seed {seed})")

    st_cfg = config.get("store", {})
    sched = loadgen.Schedule(traffic, data, seed, seconds,
                             int(st_cfg.get("live_inserted", 0)))
    model = check.Model(data, sched.all_students())
    plans = os.path.join(cache_root, f"{workload}.plans.json")
    engine_kw = dict(warmup_path=plans if os.path.exists(plans) else None,
                     tracer=Tracer(ring_size=1 << 20) if trace else None)
    if partition is None:
        engine = QueryEngine(store, **engine_kw)
    else:
        from repro.core import compat
        from repro.sparql.engine import ShardedQueryEngine
        from repro.sparql.sharded_store import shard_store

        t0 = time.perf_counter()
        store = shard_store(store, chips)
        log(f"store: {partition} over {chips} chips in "
            f"{time.perf_counter() - t0:.3f} s, shard sizes "
            f"{store.shard_sizes()}")
        engine = ShardedQueryEngine(
            store, mesh=compat.make_mesh((chips,), ("shards",),
                                         devices=cell_devices),
            **engine_kw)
    # The window's server starts with an empty prepared-statement cache:
    # set-up warms the engine's compiled shapes through a server of its
    # own, and the window's reads parse and plan as a fresh server would.
    srv = SPARQLServer(engine)
    if server_wrapper is not None:
        srv = server_wrapper(srv)
    compactor = None
    try:
        for r in sched.setup_writes:
            loadgen._do_write(srv, r)
        tail_limit = int(st_cfg.get("compaction_tail_rows", 0))
        if tail_limit and store.write_stats()["tail_rows"] >= tail_limit:
            store.compact()  # the operator's policy holds in set-up too
        max_conc = (int(traffic["clients"]) if traffic["loop"] == "closed"
                    else srv.max_batch)
        widths = [1 << i for i in range(1, 8)
                  if 1 << i <= min(max_conc, srv.max_batch)]
        warm_srv = SPARQLServer(engine)
        try:
            warm_up(engine, warm_srv, sched.possible_reads(), model, widths)
            if not os.path.exists(plans):
                warm_srv.save_cache(plans)
        finally:
            warm_srv.close()
        if st_cfg.get("writes"):
            compactor = Compactor(store, int(st_cfg["compaction_tail_rows"]))
        n_c0, s_c0, hits0 = meter.snapshot()
        log(f"engine: plan cache {engine.cache_stats()}")
        log(f"set-up compiles: {n_c0} backend compiles in {s_c0:.3f} s, "
            f"{hits0} persistent-cache hits ({cache_dir})")
        stats0 = srv.stats()
        n_traces0 = engine.tracer.n_traces if trace else 0
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
        if trace:
            jax.profiler.start_trace(trace_dir)
        t_open = time.perf_counter()
        setup_s = t_open - t_process
        out: dict = {}

        def drive():
            if traffic["loop"] == "closed":
                out["log"] = loadgen.run_closed(srv, sched, t_open, seconds)
            else:
                out["log"] = loadgen.run_open(
                    srv, sched, t_open, seconds, int(traffic["workers"]),
                    compactor.note_write if compactor else None)

        sender = threading.Thread(target=drive, daemon=True)
        with jax.profiler.TraceAnnotation("window"):
            sender.start()
            time.sleep(max(0.0, t_open + seconds - time.perf_counter()))
        t_close = time.perf_counter()
        n_c1, _, _ = meter.snapshot()
        log(f"window: {seconds} s, {n_c1 - n_c0} compiles inside it")
        sender.join(timeout=loadgen.LATE_S + 30)
        if sender.is_alive():
            raise RuntimeError("requests still outstanding a minute past "
                               "the window's close")
        stats1 = srv.stats()
        log(f"engine after the window: plan cache {engine.cache_stats()}, "
            f"store {store.write_stats()}")
        if compactor is not None:
            compactor.close()
        reduced = phased = None
        if trace:
            jax.profiler.stop_trace()
            import phases
            import xplane

            path = xplane.find_xplane(trace_dir)
            if path:
                planes = phases.load(path)
                reduced = xplane.reduce_planes(planes)
                phased = phases.reduce_planes(planes, engine.op_scopes())
                del planes
            shutil.rmtree(trace_dir, ignore_errors=True)
            if reduced is None and require_platform:
                raise RuntimeError("the profiler trace holds no device "
                                   "operation inside the window")
        peaks_per_device = [
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in cell_devices]
        peak = max(peaks_per_device)
        traces = (engine.tracer.recent()[n_traces0 - engine.tracer.n_traces:]
                  if trace and engine.tracer.n_traces > n_traces0 else [])
        store_rows = (check.store_rows_in_model_ids(model, store)
                      if st_cfg.get("writes") else None)
    finally:
        if compactor is not None:
            compactor.close()
        srv.close()
    reqlog = out.get("log", [])
    for r in [r for r in reqlog if not r.ok][:5]:
        log(f"failed {r.kind} {r.name} due {r.t_from - t_open:.3f} s "
            f"after the window opened: {r.error}")
    lateness = [r.t_send - r.t_from for r in reqlog if r.t_send]
    log(f"generator lateness p95: "
        f"{1e3 * float(np.percentile(lateness, 95)) if lateness else 0.0:.3f}"
        f" ms over {len(lateness)} requests")
    log(f"peak_bytes_in_use: {peaks_per_device}")
    del engine, store
    t_ref = time.perf_counter()
    checks = check.check(model, reqlog, sched.setup_writes, store_rows)
    log(f"reference and comparison: {time.perf_counter() - t_ref:.3f} s")

    peaks = loadgen.load_json("peaks.json")["devices"]
    ctx = Ctx(
        setup_s=setup_s, log=reqlog,
        t_open=t_open, t_close=t_close, stats0=stats0, stats1=stats1,
        compiles_in_window=n_c1 - n_c0,
        compactions=[c for c in (compactor.done if compactor else [])
                     if t_open <= c[0] < t_close],
        traces=traces, device=reduced, device_kind=dev.device_kind,
        least_bytes=({t: least_bytes(model, t)
                      for t in {r.text for r in reqlog if r.kind == "read"}}
                     if trace else {}),
        peaks=peaks, phases=phased)
    if report is not None:
        answered = [r for r in reqlog if r.ok and r.t_done <= t_close]
        report.update(
            offered_per_s=len(reqlog) / seconds,
            answered_per_s=len(answered) / (t_close - t_open),
            lateness_p95_ms=1e3 * percentile(lateness, 95) if lateness else 0,
            compactions=len(ctx.compactions),
            window_compiles=ctx.compiles_in_window,
            distinct_reads=len({r.text for r in reqlog if r.kind == "read"}),
            prepared_misses=(stats1["prepared_cache"]["misses"]
                             - stats0["prepared_cache"]["misses"]),
            phases=phased)
    metrics = {}
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    for name in metric_names(bench, workload, per_layer=trace):
        v = read_metric(name, ctx)
        if v is not None:
            metrics[name] = {"value": v, "unit": units[name]}
    correct = all(v <= lim for v, lim in checks.values())
    result = {
        "correct": correct,
        "attempted": len(reqlog),
        "failed": sum(not r.ok for r in reqlog),
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": peak,
                   "memory_peak_bytes_per_device": peaks_per_device},
    }
    if trace and reduced is not None:
        result["device"]["busy_s"] = reduced.busy_s
        result["device"]["window_s"] = reduced.window_s
        result["breakdown"] = {"device_ops": reduced.device_ops,
                               "idle_gaps": reduced.idle_gaps}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def latencies_ms(ctx: Ctx, kind: str) -> list[float]:
    """Latency of every request of `kind` sent in the window that was
    answered, from its send (closed loop) or its due time (open loop)."""
    return [1e3 * (r.t_done - r.t_from) for r in ctx.log
            if r.kind == kind and r.ok]


def answered_reads(ctx: Ctx) -> int:
    """Reads answered without an error inside the window."""
    return sum(1 for r in ctx.log if r.kind == "read" and r.ok
               and r.t_done <= ctx.t_close)


def percentile(values, q: float):
    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def median(values):
    return float(statistics.median(values)) if values else None
