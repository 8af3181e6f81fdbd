"""The MapSQ query engine (Figure 1 of the paper) and its prepared-query API.

Coprocessing split, exactly as the paper describes it:
  CPU  — parse, dictionary-encode, optimize (sparql/optimizer.py:
         statistics-driven join order, filter pushdown, projection
         pruning), size capacities, dispatch subqueries (this file,
         host Python);
  GPU→TPU — pattern range-scans feed the MapReduce join (Algorithm 1,
         core/mr_join.py, jitted).

The public API is layered around prepared queries:

  engine.prepare(text) -> PreparedQuery   parse + validate + plan once
  pq.run()             -> ResultSet       typed rows + the run's ExecStats
  pq.explain()         -> str             algebra tree, physical plan,
                                          bucket capacities, cache state
  engine.query(text)   -> list[dict]      thin wrapper: prepare().run().rows
  engine.run_batch(ps) -> list[ResultSet] micro-batch execution: same-shape
                                          queries coalesce into stacked
                                          (vmapped) device dispatches —
                                          N warm same-shape queries cost
                                          ceil(N / width) launches
  engine.update(text)  -> UpdateResult    INSERT DATA / DELETE DATA against
                                          the store's delta blocks; warm
                                          plan shapes survive the write
  engine.stats()       -> dict            plan cache + scan cache + the
                                          store's write-path health

Two execution modes share one planner:

  compiled (default) — plan → plan-cache lookup → ONE device dispatch. The
      whole operator tree (joins, OPTIONAL left joins, FILTER masks,
      projection, DISTINCT, LIMIT/OFFSET) is lowered by core/executor.py
      into a single AOT-compiled program, cached by (plan shape, bucket
      signature) in a PlanCache. FILTER constants and LIMIT/OFFSET are
      runtime inputs, so query variants share the executable. A cache miss
      first runs the eager evaluator once: its Mars count passes double as
      the capacity *calibration* that picks the pow-2 join buckets the
      program is compiled at. Warm queries then run with zero compiles and
      no per-join host sync (the only sync reads the overflow flags that
      ride back with the results). If a bucket overflows (a same-shape
      query with a bigger result), the engine grows the bucket from the
      exact totals returned by the dispatch and recompiles — the
      double-on-overflow retry demoted to a host-level fallback.

  eager (compiled=False) — the per-operator loop, kept for differential
      testing: per join, a jitted COUNT pass, host sync of the cardinality,
      exactly-sized (next-pow2) buffer, jitted EXPAND pass; or
      double-on-overflow when exact_count_pass=False.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import pathlib
import threading
import time
import weakref
from collections import OrderedDict

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import compat
from repro.core import executor as ex
from repro.core import mr_join as mj
from repro.core import plan_ir
from repro.core.planner import TriplePattern
from repro.core.relation import UNBOUND, Relation
from repro.obs import MetricsRegistry, Tracer
from repro.obs.trace import phase
from repro.sparql import algebra, optimizer
from repro.sparql.parser import Query, UpdateRequest, parse, parse_update
from repro.sparql.store import TripleStore, _next_pow2

# LIMIT stand-in when only OFFSET was given (far above max_capacity, safe
# from int32 overflow in `offset + limit`).
_NO_LIMIT = 1 << 30


@dataclasses.dataclass
class ExecStats:
    n_joins: int = 0
    n_count_passes: int = 0
    n_retries: int = 0
    peak_capacity: int = 0
    peak_join_bucket: int = 0  # largest intermediate join bucket this run
    # compiled-pipeline accounting
    cache_hits: int = 0
    cache_misses: int = 0
    n_compiles: int = 0  # XLA compilations triggered by this query
    n_dispatches: int = 0  # device program launches (warm target: 1)
    # stacked-batch accounting: width of the vmapped dispatch that served
    # this run (0 = solo). Batchmates share one dispatch, so their
    # n_dispatches/n_compiles report the chunk's shared counts.
    batch_width: int = 0
    # the store version this run's scans were staged at (-1 = not set):
    # the snapshot the results are consistent with
    store_version: int = -1
    # sharded-execution data movement (zero on the single-device engine):
    # shuffle collectives the lowering emitted vs elided because the input
    # was already hash-partitioned on the join key, and small-side
    # broadcast (all_gather) joins
    n_shuffles_emitted: int = 0
    n_shuffles_elided: int = 0
    n_broadcast_joins: int = 0
    # host wall seconds spent inside device dispatch + result sync for
    # THIS run (the engine-level `device_time_s` is the sum of these)
    device_time_s: float = 0.0
    # rows this run's decode emitted (-1 = not yet decoded)
    rows_emitted: int = -1
    # EXPLAIN ANALYZE actuals, in join-slot (evaluation) order — the same
    # order as plan.join_ests/join_caps. Captured from the exact totals
    # that ride back with every dispatch:
    #   join_totals    global matched rows per join slot
    #   join_worst     worst single shard/lane per slot (fill pressure)
    #   join_overflows overflow->regrow events per slot (summed)
    #   join_caps      bucket capacity the final (successful) run used
    #   shuffle_loads  worst per-shard shuffle rows per shuffle slot
    join_totals: tuple[int, ...] = ()
    join_worst: tuple[int, ...] = ()
    join_overflows: tuple[int, ...] = ()
    join_caps: tuple[int, ...] = ()
    shuffle_loads: tuple[int, ...] = ()

    def add(self, other: "ExecStats") -> None:
        self.n_joins += other.n_joins
        self.n_count_passes += other.n_count_passes
        self.n_retries += other.n_retries
        self.peak_capacity = max(self.peak_capacity, other.peak_capacity)
        self.peak_join_bucket = max(
            self.peak_join_bucket, other.peak_join_bucket
        )
        self.cache_hits += other.cache_hits
        self.cache_misses += other.cache_misses
        self.n_compiles += other.n_compiles
        self.n_dispatches += other.n_dispatches
        self.batch_width = max(self.batch_width, other.batch_width)
        self.store_version = max(self.store_version, other.store_version)
        self.n_shuffles_emitted += other.n_shuffles_emitted
        self.n_shuffles_elided += other.n_shuffles_elided
        self.n_broadcast_joins += other.n_broadcast_joins
        self.device_time_s += other.device_time_s
        if other.rows_emitted >= 0:
            self.rows_emitted = other.rows_emitted
        # actuals: last run wins (pq.stats accumulates across runs but
        # the analyze view reports the most recent execution); overflow
        # events accumulate
        if other.join_totals:
            self.join_totals = other.join_totals
            self.join_worst = other.join_worst
            self.join_caps = other.join_caps
            self.shuffle_loads = other.shuffle_loads
        if other.join_overflows:
            mine = self.join_overflows
            if len(mine) == len(other.join_overflows):
                self.join_overflows = tuple(
                    a + b for a, b in zip(mine, other.join_overflows)
                )
            else:
                self.join_overflows = other.join_overflows


@dataclasses.dataclass
class PlanCacheEntry:
    shape: plan_ir.PlanShape
    join_caps: tuple[int, ...]
    compiled: ex.CompiledPlan
    # (width, per-scan stacked/broadcast axes) -> stacked executable at
    # THESE join caps (compiled on demand by run_batch; reset when an
    # overflow regrow replaces the entry)
    batched: dict[tuple, ex.CompiledBatch] = dataclasses.field(
        default_factory=dict
    )
    # (width, axes) layouts persisted by a previous process (save_cache
    # round-trips them even before this process serves a stacked batch);
    # pre-layout files carried widths only — those load as all-stacked
    warm_layouts: tuple[tuple, ...] = ()
    # numeric-value table length the executable was lowered against
    # (0 = unchecked). Inserts that grow the dictionary past a pow-2
    # boundary change that shape; the engine recompiles the entry at the
    # same join caps when it notices the mismatch.
    num_cap: int = 0

    def widths(self) -> tuple[int, ...]:
        """Known stacked widths for this signature: compiled this process
        (at any scan layout) plus persisted from the warmup file."""
        return tuple(
            sorted(
                {k[0] for k in self.batched}
                | {w for w, _ in self.warm_layouts}
            )
        )

    def layouts(self) -> tuple[tuple, ...]:
        """Known (width, scan_axes) stacked layouts for this signature."""
        return tuple(
            sorted(
                set(self.batched) | set(self.warm_layouts),
                key=lambda k: (k[0], str(k[1])),
            )
        )


class PlanCache:
    """(plan shape, bucket signature) -> compiled executable, FIFO-bounded."""

    def __init__(self, max_entries: int = 256):
        self.max_entries = max_entries
        self._entries: OrderedDict[plan_ir.PlanShape, PlanCacheEntry] = (
            OrderedDict()
        )
        self.hits = 0
        self.misses = 0
        self.compiles = 0

    def get(self, shape: plan_ir.PlanShape) -> PlanCacheEntry | None:
        return self._entries.get(shape)

    def put(self, shape: plan_ir.PlanShape, entry: PlanCacheEntry) -> None:
        self._entries[shape] = entry
        while len(self._entries) > self.max_entries:
            self._entries.popitem(last=False)

    def __len__(self) -> int:
        return len(self._entries)

    def entries(self) -> list[PlanCacheEntry]:
        return list(self._entries.values())

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def stats(self) -> dict:
        return {
            "hits": self.hits,
            "misses": self.misses,
            "compiles": self.compiles,
            "entries": len(self._entries),
            "hit_rate": self.hit_rate,
        }


@dataclasses.dataclass
class BatchGroupStats:
    """run_batch accounting for one plan group (shared PlanShape).

    `n_dispatches` counts every device launch the group made — stacked
    chunks, overflow retries, and the sequential calibration run of a cold
    group — so ceil(N/width) is directly assertable. `widths` lists the
    bucketed lane width of each stacked chunk, in dispatch order."""

    n_queries: int
    widths: tuple[int, ...] = ()
    n_dispatches: int = 0
    n_compiles: int = 0
    cold: bool = False  # group paid calibration/compilation this batch
    fallback: bool = False  # stacked dispatch failed; ran sequentially
    # scan positions shipped ONCE (vmap in_axes=None) because every lane's
    # pattern was identical — the same-query-different-FILTER win: those
    # buffers skip the W-copy stacking entirely
    n_broadcast_scans: int = 0
    # cross-shape padding: this group coalesced `n_shapes` near-miss
    # PlanShapes (same plan DAG, smaller pow-2 scan caps) into one stacked
    # signature by padding every lane's scans up to the group's max caps
    padded: bool = False
    n_shapes: int = 1


@dataclasses.dataclass
class _Program:
    """A planned query: scan order, join structure, runtime constants.

    This is the engine-internal bridge from the optimizer's output to a
    PlanShape; a PreparedQuery owns one and reuses it across runs.
    """

    query: Query
    plan: optimizer.OptimizedProgram  # optimizer output incl. trace/ests
    patterns: list[TriplePattern]  # scan order: required, groups, branches
    cross_flags: tuple[bool, ...]  # required chain
    opt_groups: tuple[plan_ir.GroupSpec, ...]
    union_groups: tuple[plan_ir.GroupSpec, ...]
    has_required: bool
    filters: tuple[plan_ir.FilterSpec, ...]  # staged, original var names
    n_consts: tuple[int, int]  # (int, float) filter consts (sans slice)
    consts_i: np.ndarray  # int32: filter term ids (+ offset, limit)
    consts_f: np.ndarray  # float32: numeric filter constants
    projection: tuple[str, ...]
    distinct: bool
    has_slice: bool


@dataclasses.dataclass
class _BatchCtx:
    """Per-query HOST staging for run_batch: the program, its plan-cache
    key and the canonical->original name mapping. Deliberately holds no
    device arrays — scans are re-fetched from the store's bounded caches
    per batch, so a cached PreparedQuery handle never pins device buffers
    past the scan cache's eviction policy. `store_version` records the
    version the shape was computed at: a write can move a pattern into a
    bigger capacity bucket, so a stale ctx is recomputed before grouping."""

    prog: _Program
    shape: plan_ir.PlanShape
    inverse: dict[str, str]
    store_version: int = -1


class ResultSet:
    """Typed, decoded query result: rows as {var: term} dicts (variables an
    OPTIONAL group left unbound are omitted), plus the producing run's
    ExecStats. Compares equal to a plain list of row dicts for convenience.
    """

    def __init__(self, vars: tuple[str, ...], rows: list[dict[str, str]],
                 stats: ExecStats):
        self.vars = tuple(vars)
        self.rows = rows
        self.stats = stats

    def __len__(self) -> int:
        return len(self.rows)

    def __iter__(self):
        return iter(self.rows)

    def __getitem__(self, i):
        return self.rows[i]

    def __eq__(self, other) -> bool:
        if isinstance(other, ResultSet):
            return self.rows == other.rows
        if isinstance(other, list):
            return self.rows == other
        return NotImplemented

    def __repr__(self) -> str:
        return f"ResultSet(vars={self.vars}, n_rows={len(self.rows)})"


class _Stage:
    """One dispatch's staging: the store's snapshot lock, the scans staged
    under it and the runtime-constant upload, inside the `mapsq.stage`
    annotation when the engine has a tracer. `timed` (a traced request
    rides the dispatch) also times the interval (`t0`, `t1`) and the wait
    for the lock (`lock_wait_s`); untimed, it reads no clock."""

    __slots__ = ("_store", "_ann", "timed", "t0", "t1", "lock_wait_s")

    def __init__(self, engine: "QueryEngine", timed: bool):
        self._store = engine.store
        self._ann = phase(engine.tracer, "stage")
        self.timed = timed
        self.t0 = self.t1 = self.lock_wait_s = 0.0

    def __enter__(self) -> "_Stage":
        self._ann.__enter__()
        if self.timed:
            self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        if self.timed:
            self.t1 = time.perf_counter()
        self._ann.__exit__(*exc)

    @contextlib.contextmanager
    def locked(self):
        lock = self._store.snapshot_lock()
        if not self.timed:
            with lock:
                yield
            return
        t = time.perf_counter()
        with lock:
            self.lock_wait_s += time.perf_counter() - t
            yield

    def attrs(self) -> dict:
        return {"lock_wait_ms": round(self.lock_wait_s * 1e3, 3)}


class _SharedFetch:
    """One device→host transfer shared by every lane of a stacked chunk.

    The transfer is LAZY: the batcher thread hands lanes to the decode
    pool holding only device references; whichever decode worker resolves
    its lane first pays the (single) `np.asarray` sync, and the device
    buffers are dropped immediately after so a slow decode queue never
    pins a chunk's device memory longer than one transfer."""

    __slots__ = ("_lock", "_rel", "cols", "valid", "transfer_s")

    def __init__(self, rel: Relation):
        self._lock = threading.Lock()
        self._rel: Relation | None = rel
        self.cols: np.ndarray | None = None
        self.valid: np.ndarray | None = None
        self.transfer_s = 0.0

    def fetch(self) -> tuple[np.ndarray, np.ndarray, bool]:
        """Returns (cols, valid, paid): `paid` is True for the one caller
        that performed the device->host sync, False for sharers."""
        with self._lock:
            if self._rel is not None:
                t0 = time.perf_counter()
                self.cols = np.asarray(self._rel.cols)
                self.valid = np.asarray(self._rel.valid)
                self.transfer_s = time.perf_counter() - t0
                self._rel = None
                return self.cols, self.valid, True
        return self.cols, self.valid, False


class PendingDecode:
    """A dispatched query's undecoded result: result buffers (device-side
    until the first consumer fetches) plus the lane metadata needed to
    materialise rows.

    This is the unit the serving pipeline passes from the dispatch stage
    to the decode stage — `run_batch_pipelined` returns one per slot, and
    `resolve()` (the transfer + row decode + per-handle accounting) runs
    on a decode worker, overlapping the batcher thread's next dispatch.
    `lane` selects this query's slice of a stacked chunk (None for a solo
    run whose buffers are already 2-D). A traced slot notes when its
    dispatch finished (`t_ready`): its `decode_wait` span runs from there
    to the decode worker taking it up — the batch's later groups, the
    hand-off and the decode pool's queue."""

    __slots__ = ("engine", "pq", "vars", "names", "fetch", "lane", "stats",
                 "trace", "t_ready")

    def __init__(self, engine: "QueryEngine", pq: "PreparedQuery",
                 vars: tuple[str, ...], names: tuple[str, ...],
                 fetch: _SharedFetch, lane: "int | None", stats: ExecStats,
                 trace=None):
        self.engine = engine
        self.pq = pq
        self.vars = vars
        self.names = names
        self.fetch = fetch
        self.lane = lane
        self.stats = stats
        self.trace = trace
        self.t_ready = time.perf_counter() if trace is not None else 0.0

    def resolve(self) -> ResultSet:
        tracer = self.engine.tracer
        t0 = time.perf_counter()
        with phase(tracer, "transfer"):
            cols, valid, paid = self.fetch.fetch()
        t1 = time.perf_counter()
        with phase(tracer, "decode"):
            if self.lane is not None:
                cols, valid = cols[self.lane], valid[self.lane]
            rows = self.engine._decode_numpy(self.names, cols[valid])
        t2 = time.perf_counter()
        if self.trace is not None:
            self.trace.add_span("decode_wait", self.t_ready, t0)
            # the sharing lanes' "transfer" span is their wait on the
            # paying lane's sync (usually ~0): attrs distinguish them
            self.trace.add_span("transfer", t0, t1, paid=paid,
                                transfer_s=round(self.fetch.transfer_s, 6))
            self.trace.add_span("decode", t1, t2, rows=len(rows))
        self.stats.rows_emitted = len(rows)
        pq = self.pq
        pq.stats.add(self.stats)
        pq.last_stats = self.stats
        pq.n_runs += 1
        return ResultSet(self.vars, rows, self.stats)


class PreparedQuery:
    """A parsed, validated and planned query, reusable across runs.

    Holds per-handle accounting: `stats` accumulates ExecStats over every
    run (peak_capacity as a running max), `last_stats` is the most recent
    run's. The compiled executable itself lives in the engine's PlanCache,
    shared by every handle (and every client) with the same plan shape.
    """

    def __init__(self, engine: "QueryEngine", text: str, query: Query):
        self.engine = engine
        self.text = text
        self.query = query
        self._program = engine._build_program(query)
        self._batch_ctx: _BatchCtx | None = None  # run_batch staging cache
        self.stats = ExecStats()  # accumulated across runs
        self.last_stats: ExecStats | None = None
        self.n_runs = 0
        # the store version this handle was planned against. Runs stay
        # CORRECT regardless (scans re-stage at the current version each
        # run, under the store's snapshot lock); the pin records which
        # statistics the optimizer's choices reflect — see refresh().
        self.planned_version = engine.store.version

    def refresh(self) -> bool:
        """Re-plan against the store's current statistics if data changed
        since this handle was planned (or last refreshed).

        Optional: run() results are always computed on the live snapshot;
        refresh only updates the optimizer's join-order/backend choices
        (and this handle's pinned version). Returns True if re-planned."""
        if self.planned_version == self.engine.store.version:
            return False
        self._program = self.engine._build_program(self.query)
        self._batch_ctx = None
        self.planned_version = self.engine.store.version
        return True

    def run(self, trace=None) -> ResultSet:
        return self._run_pending(trace).resolve()

    def _run_pending(self, trace=None) -> PendingDecode:
        """Dispatch the query, returning its result as a PendingDecode:
        device work is enqueued, host decode is not yet paid. run() is
        `_run_pending().resolve()`; the pipelined server resolves on a
        decode worker instead."""
        stats = ExecStats()
        rel = self.engine._execute_program(self._program, stats, trace)
        return PendingDecode(
            self.engine, self, self._program.projection, rel.schema,
            _SharedFetch(rel), None, stats, trace,
        )

    def explain(self, analyze: bool = False) -> str:
        """The plan explanation; `analyze=True` appends per-join-node
        actuals (estimated vs actual rows, bucket fill, overflows, the
        chosen backend) from the most recent run — running the query once
        first if this handle has never executed."""
        if analyze and self.last_stats is None:
            self.run()
        return self.engine._explain_program(self, self._program,
                                            analyze=analyze)


@dataclasses.dataclass
class UpdateResult:
    """Outcome of engine.update(): rows actually applied (set semantics —
    duplicate inserts and absent deletes are skipped) and the store
    version the update committed at."""

    inserted: int
    deleted: int
    n_ops: int
    version: int


@dataclasses.dataclass
class QueryEngine:
    store: TripleStore
    use_kernel: bool = False  # Pallas pair-expand in the join
    exact_count_pass: bool = True  # Mars two-pass vs double-on-overflow
    max_capacity: int = 1 << 24
    compiled: bool = True  # one-dispatch compiled pipeline vs eager loop
    plan_cache_entries: int = 256
    optimize: bool = True  # cost-based optimizer (False: legacy greedy)
    # physical join algebra: None = per-node cost-based choice (the
    # optimizer's selectivity x skew rule), "mr" / "matrix" = force every
    # join slot onto that backend (differential tests, benchmarks)
    join_backend: str | None = None
    warmup_path: str | None = None  # saved bucket signatures (save_cache)
    max_batch_width: int = 64  # lane cap per stacked run_batch dispatch
    # cross-shape padded stacking: run_batch coalesces near-miss PlanShapes
    # (identical but for pow-2 scan caps) into one stacked dispatch by
    # padding scans up to the group's max caps — padding rows are
    # valid=False, hence invisible to every masked operator. Merges are
    # taken only when every member shape is already warm and the padding
    # waste stays under pad_waste_limit (padded/real cell ratio - 1).
    pad_stacking: bool = True
    pad_waste_limit: float = 2.0
    # per-query span tracing: None (default) = off, zero overhead beyond
    # `trace is not None` checks on the dispatch path. The server shares
    # this Tracer so its request spans and the engine's dispatch spans
    # land in one trace tree.
    tracer: Tracer | None = None

    def __post_init__(self):
        if self.join_backend not in (None, "mr", "matrix"):
            raise ValueError(
                f"join_backend must be None, 'mr' or 'matrix' "
                f"(got {self.join_backend!r})"
            )
        self._jit_join = jax.jit(
            mj.mr_join, static_argnames=("capacity", "use_kernel")
        )
        self._jit_left_join = jax.jit(
            mj.left_join, static_argnames=("capacity", "use_kernel")
        )
        self._jit_count = jax.jit(mj.mr_join_count)
        self._jit_cross = jax.jit(mj.cross_join, static_argnames=("capacity",))
        self.plan_cache = PlanCache(self.plan_cache_entries)
        # executable -> its op_scopes() key (traced launches name it)
        self._module_keys: weakref.WeakKeyDictionary = (
            weakref.WeakKeyDictionary()
        )
        # learned bucket signatures from a previous process: a shape found
        # here compiles directly at the saved capacities, skipping the
        # eager calibration run entirely
        self._warm_caps: dict[plan_ir.PlanShape, tuple[int, ...]] = {}
        # persisted stacked (width, scan_axes) layouts per shape; files
        # written before run_batch existed simply have none, and files
        # from before broadcast scans carry widths only (all-stacked)
        self._warm_layouts: dict[plan_ir.PlanShape, tuple[tuple, ...]] = {}
        if self.warmup_path is not None:
            p = pathlib.Path(self.warmup_path)
            if p.exists():
                data = json.loads(p.read_text())
                # v3 files carry the writer's statistics catalog: seed the
                # store's lazy cache with it so backend choices (hence plan
                # shapes) match the saved signatures exactly. Older files
                # (v1/v2) have no catalog — the store computes its own,
                # which is identical for the same triples.
                stats_blob = data.get("statistics")
                if stats_blob is not None and self.store._statistics is None:
                    from repro.sparql.store import StoreStatistics

                    self.store._statistics = StoreStatistics.from_jsonable(
                        stats_blob
                    )
                for e in data["entries"]:
                    shape = plan_ir.shape_from_jsonable(e["shape"])
                    self._warm_caps[shape] = tuple(
                        int(c) for c in e["join_caps"]
                    )
                    layouts = [
                        (int(w), tuple(axes))
                        for w, axes in e.get("layouts", ())
                    ]
                    stacked = (0,) * len(shape.scan_schemas)
                    for w in e.get("widths", ()):
                        if not any(lw == int(w) for lw, _ in layouts):
                            layouts.append((int(w), stacked))
                    if layouts:
                        self._warm_layouts[shape] = tuple(layouts)
        # stacked-batch counters (cumulative; server stats report them)
        self.batch_width_hist: dict[int, int] = {}
        self.stacked_dispatches = 0
        self.stacked_queries = 0
        # stacked chunks re-run one query at a time after a lane's regrow
        # passed max_capacity
        self.stacked_fallbacks = 0
        self.last_batch: list[BatchGroupStats] = []
        # cross-shape padding counters: merges taken / rejected by the
        # cost guard, and the cell ledger behind the waste ratio
        # (padded_cells ≥ real_cells; their gap is what padding burned)
        self.padded_groups = 0
        self.pad_rejects = 0
        self.padded_cells = 0
        self.real_cells = 0
        # cumulative wall seconds the host spent inside device dispatch +
        # result sync — the open-loop bench derives the device-idle
        # fraction as 1 - Δdevice_time_s / wall
        self.device_time_s = 0.0
        # correlates the N lane "dispatch" spans a stacked chunk fans out
        self._dispatch_seq = 0
        # the unified metrics registry: engine-side counters are bridged
        # in by a scrape-time collector (the dispatch path pays nothing);
        # the server registers its request metrics on this same registry
        self.metrics = MetricsRegistry()
        self._register_metrics()

    def _register_metrics(self) -> None:
        """Declare the engine's metrics and the collector that mirrors
        the hot-path counters into them at scrape time (naming scheme:
        mapsq_<subsystem>_<name>[_total|_seconds|_ratio])."""
        m = self.metrics
        g = {
            "plan_hits": m.counter(
                "mapsq_plan_cache_hits_total", "plan cache hits"),
            "plan_misses": m.counter(
                "mapsq_plan_cache_misses_total", "plan cache misses"),
            "plan_compiles": m.counter(
                "mapsq_plan_cache_compiles_total", "XLA compilations"),
            "plan_entries": m.gauge(
                "mapsq_plan_cache_entries", "live plan cache entries"),
            "scan_hits": m.counter(
                "mapsq_scan_cache_hits_total", "scan cache hits"),
            "scan_misses": m.counter(
                "mapsq_scan_cache_misses_total", "scan cache misses"),
            "scan_evictions": m.counter(
                "mapsq_scan_cache_evictions_total",
                "scan cache entries dropped by writes"),
            "stacked_dispatches": m.counter(
                "mapsq_stacked_dispatches_total",
                "vmapped multi-query device launches"),
            "stacked_queries": m.counter(
                "mapsq_stacked_queries_total",
                "queries served by stacked launches"),
            "stacked_fallbacks": m.counter(
                "mapsq_stacked_fallbacks_total",
                "stacked chunks re-run sequentially after a capacity "
                "overflow"),
            "padded_groups": m.counter(
                "mapsq_padding_groups_total",
                "cross-shape padded merges taken"),
            "pad_rejects": m.counter(
                "mapsq_padding_rejects_total",
                "padded merges rejected by the waste guard"),
            "padded_cells": m.counter(
                "mapsq_padding_padded_cells_total",
                "scan cells dispatched incl. padding"),
            "real_cells": m.counter(
                "mapsq_padding_real_cells_total",
                "scan cells that were real data"),
            "device_time": m.counter(
                "mapsq_device_time_seconds_total",
                "host wall seconds inside device dispatch + sync"),
            "store_version": m.gauge(
                "mapsq_store_version", "store write version"),
            "store_tail": m.gauge(
                "mapsq_store_tail_rows", "uncompacted delta rows"),
            "store_tombstones": m.gauge(
                "mapsq_store_tombstones", "live tombstone rows"),
        }
        g["traces"] = m.counter(
            "mapsq_traces_total", "finished query traces")
        g["slow"] = m.counter(
            "mapsq_slow_queries_total",
            "traces over the slow-query threshold")

        def collect() -> None:
            pc = self.plan_cache.stats()
            g["plan_hits"].set_total(pc["hits"])
            g["plan_misses"].set_total(pc["misses"])
            g["plan_compiles"].set_total(pc["compiles"])
            g["plan_entries"].set(pc["entries"])
            sc = self.store.scan_cache_stats()
            g["scan_hits"].set_total(sc.get("hits", 0))
            g["scan_misses"].set_total(sc.get("misses", 0))
            g["scan_evictions"].set_total(sc.get("evictions", 0))
            g["stacked_dispatches"].set_total(self.stacked_dispatches)
            g["stacked_queries"].set_total(self.stacked_queries)
            g["stacked_fallbacks"].set_total(self.stacked_fallbacks)
            g["padded_groups"].set_total(self.padded_groups)
            g["pad_rejects"].set_total(self.pad_rejects)
            g["padded_cells"].set_total(self.padded_cells)
            g["real_cells"].set_total(self.real_cells)
            g["device_time"].set_total(self.device_time_s)
            ws = self.store.write_stats()
            g["store_version"].set(ws["version"])
            g["store_tail"].set(ws["tail_rows"])
            g["store_tombstones"].set(ws["tombstones"])
            if self.tracer is not None:
                g["traces"].set_total(self.tracer.n_traces)
                g["slow"].set_total(self.tracer.n_slow)

        m.register_collector(collect)

    def _device_tick(self, stats: ExecStats, t0: float) -> float:
        """Account one dispatch-and-sync interval on BOTH ledgers (the
        engine-wide total and this run's ExecStats) so the engine total
        always equals the sum over runs. Returns the end stamp."""
        t1 = time.perf_counter()
        dt = t1 - t0
        self.device_time_s += dt
        stats.device_time_s += dt
        return t1

    def render_prometheus(self) -> str:
        return self.metrics.render_prometheus()

    def save_cache(self, path: str) -> int:
        """Serialize the plan cache's learned bucket signatures to JSON.

        A `QueryEngine(warmup_path=...)` in a restarted process compiles
        known shapes straight at these capacities — no calibration run.
        Each entry carries the stacked batch widths seen for the shape
        (compiled this process or inherited from a previous warmup file),
        so (shape, caps, width) signatures round-trip across restarts;
        files written before batching existed load unchanged (the widths
        key is optional). Returns the number of signatures written.
        """
        entries = [
            self._entry_jsonable(e) for e in self.plan_cache.entries()
        ]
        pathlib.Path(path).write_text(
            json.dumps(
                {
                    "version": 3,
                    # the statistics catalog (incl. per-predicate degree
                    # skew) rides along so a restarted process makes the
                    # SAME backend decisions — shapes keep hashing to the
                    # saved signatures even if it recomputes nothing
                    "statistics": self.store.statistics.to_jsonable(),
                    "entries": entries,
                }
            )
        )
        return len(entries)

    def _entry_jsonable(self, e: PlanCacheEntry) -> dict:
        """One warmup-file entry (the sharded engine appends its shuffle
        bucket caps here — keep the base format in one place)."""
        return {
            "shape": plan_ir.shape_to_jsonable(e.shape),
            "join_caps": list(e.join_caps),
            "widths": list(e.widths()),
            "layouts": [[w, list(axes)] for w, axes in e.layouts()],
        }

    # -- public API --------------------------------------------------------
    def prepare(self, text: str, trace=None) -> PreparedQuery:
        """Parse, validate and plan once; run (and re-run) later."""
        if trace is None:
            return PreparedQuery(self, text, parse(text))
        with trace.span("parse"):
            q = parse(text)
        with trace.span("optimize"):
            return PreparedQuery(self, text, q)

    def query(self, text: str) -> list[dict[str, str]]:
        """One-shot convenience: rows as {var: term} dicts."""
        return self.prepare(text).run().rows

    def execute(self, q: Query) -> tuple[Relation, ExecStats]:
        """Run a parsed query; the result Relation carries the projected
        (and DISTINCT-deduplicated, filtered, sliced) bindings."""
        stats = ExecStats()
        rel = self._execute_program(self._build_program(q), stats)
        return rel, stats

    def explain(self, text: str, analyze: bool = False) -> str:
        return self.prepare(text).explain(analyze=analyze)

    def update(self, text: str) -> UpdateResult:
        """Parse and apply `INSERT DATA { ... }` / `DELETE DATA { ... }`
        operations, in request order, atomically against queries (the
        whole request holds the store's write lock, so no run observes a
        half-applied request).

        Warm plan shapes survive the write: inserted rows and tombstone
        masks ride inside the existing pow-2 scan buckets, so previously
        compiled programs keep re-running at 0 compiles / 1 dispatch until
        a pattern outgrows its bucket."""
        req: UpdateRequest = parse_update(text)
        inserted = deleted = 0
        with self.store.snapshot_lock():
            for op in req.ops:
                rows = [(tp.s, tp.p, tp.o) for tp in op.triples]
                if isinstance(op, algebra.InsertData):
                    inserted += self.store.insert_triples(rows)
                else:
                    deleted += self.store.delete_triples(rows)
        return UpdateResult(
            inserted, deleted, len(req.ops), self.store.version
        )

    def cache_stats(self) -> dict:
        return self.plan_cache.stats()

    def op_scopes(self) -> dict[str, dict[str, str]]:
        """Module key -> {HLO instruction name: plan-operator scope} for
        every executable in the plan cache (solo and stacked widths):
        `join2/count` is the count phase of join slot 2 as EXPLAIN ANALYZE
        numbers slots. A device trace names ops by HLO instruction only;
        this is what attributes them to plan operators. An executable that
        a build without scopes compiled (a persistent-cache hit) maps every
        instruction to ''."""
        return {
            self._module_key(c.executable): ex.hlo_op_scopes(
                c.executable.as_text())
            for e in self.plan_cache.entries()
            for c in (e.compiled, *e.batched.values())
        }

    def _module_key(self, executable) -> str:
        """`ex.module_key`, once per executable: reading it deserializes the
        module, tens of ms for a large program on the TPU."""
        key = self._module_keys.get(executable)
        if key is None:
            key = self._module_keys[executable] = ex.module_key(executable)
        return key

    def _launch(self, compiled) -> "contextlib.AbstractContextManager":
        """The `mapsq.launch` phase of one executable call, naming the
        module it launches (`module_key`): what ties a device trace's "XLA
        Modules" event to its executable's `op_scopes()`."""
        if self.tracer is None:
            return phase(None, "launch")
        return phase(self.tracer, "launch",
                     module=self._module_key(compiled.executable))

    def stats(self) -> dict:
        """One observability snapshot: plan cache, scan cache, and the
        store's write-path health (version, tail size, tombstone count,
        compaction count)."""
        return {
            "plan_cache": self.plan_cache.stats(),
            "scan_cache": self.store.scan_cache_stats(),
            "store": self.store.write_stats(),
        }

    def run_batch(self, prepared: list[PreparedQuery]) -> list[ResultSet]:
        """Execute a micro-batch, coalescing same-shape queries.

        Queries are grouped by compiled plan signature (PlanShape); each
        warm group runs as ONE stacked device dispatch per pow-2 width
        chunk (vmap over scan tuples and runtime constants), so N warm
        same-shape queries cost ceil(N / width) dispatches instead of N.
        Mixed batches fall back per-group; a cold group calibrates on its
        first query and stacks the rest. Results are positionally aligned
        with `prepared`. Per-group accounting lands in `self.last_batch`;
        the first failing query's exception is re-raised (use
        `run_batch_outcomes` for per-query error isolation).
        """
        outcomes = self.run_batch_outcomes(prepared)
        for oc in outcomes:
            if isinstance(oc, Exception):
                raise oc
        return outcomes

    def run_batch_outcomes(
        self, prepared: list[PreparedQuery]
    ) -> list["ResultSet | Exception"]:
        """run_batch with per-query error isolation: each slot is either a
        ResultSet or the exception that query raised (the server's batch
        path relies on one bad query never failing its batchmates)."""
        return self._run_batch_impl(prepared, defer=False)

    def run_batch_pipelined(
        self, prepared: list[PreparedQuery], traces: "list | None" = None
    ) -> list["ResultSet | Exception | PendingDecode"]:
        """The serving pipeline's dispatch stage: like run_batch_outcomes,
        but slots whose device work dispatched cleanly come back as
        PendingDecode — the host decode (device→host transfer + row
        materialisation + per-handle accounting) has NOT been paid, and
        `.resolve()` may run on any thread. The batcher thread returns as
        soon as device work is enqueued, so dispatch of batch k+1 overlaps
        decode of batch k on the decode pool."""
        return self._run_batch_impl(prepared, defer=True, traces=traces)

    def _run_batch_impl(
        self, prepared: list[PreparedQuery], defer: bool,
        traces: "list | None" = None,
    ) -> list:
        self.last_batch = []
        out: list = [None] * len(prepared)
        if traces is None:
            traces = [None] * len(prepared)
        # a traced read's `batch_wait` runs from here to its own staging:
        # the batch's grouping and the groups dispatched before its own
        t_batch = (
            time.perf_counter() if any(t is not None for t in traces)
            else None
        )
        if not self.compiled:
            group = BatchGroupStats(n_queries=len(prepared), fallback=True)
            self.last_batch.append(group)
            for i, pq in enumerate(prepared):
                self._note_batch_wait(traces, [i], t_batch)
                out[i] = self._run_single(pq, group, defer, traces[i])
            return out
        ctxs: list[_BatchCtx | None] = [None] * len(prepared)
        with phase(self.tracer, "prepare"):
            merged = self._group_batch(prepared, out, ctxs)
        for shape, (idxs, n_shapes, n_compiles) in merged.items():
            self._run_group(
                shape, idxs, ctxs, prepared, out, defer,
                n_shapes=n_shapes, extra_compiles=n_compiles,
                traces=traces, t_batch=t_batch,
            )
        return out

    def _group_batch(
        self,
        prepared: list[PreparedQuery],
        out: list,
        ctxs: list["_BatchCtx | None"],
    ) -> "OrderedDict[plan_ir.PlanShape, tuple[list[int], int, int]]":
        """Fill each handle's batch context into `ctxs` and group the batch
        by compiled plan signature (the PlanShape cache key), merging
        near-miss shapes when padded stacking is on; a handle whose
        staging fails gets its exception in `out`."""
        groups: OrderedDict[plan_ir.PlanShape, list[int]] = OrderedDict()
        for i, pq in enumerate(prepared):
            try:
                # staging is stable per handle between writes (program,
                # cache key) — compute once, reuse across micro-batches,
                # recompute after a store version bump (a write can move a
                # pattern into a bigger capacity bucket = a new shape)
                if (
                    pq._batch_ctx is None
                    or pq._batch_ctx.store_version != self.store.version
                ):
                    pq._batch_ctx = self._batch_context(pq._program)
                ctxs[i] = pq._batch_ctx
            except Exception as e:
                out[i] = e
                continue
            groups.setdefault(ctxs[i].shape, []).append(i)
        if self.pad_stacking and len(groups) > 1:
            return self._coalesce_groups(groups)
        return OrderedDict((s, (idxs, 1, 0)) for s, idxs in groups.items())

    @staticmethod
    def _note_batch_wait(
        traces: list, idxs: list[int], t_batch: "float | None"
    ) -> None:
        """Record `batch_wait` on the traced reads about to run: from the
        batch's start to now."""
        if t_batch is None:
            return
        now = time.perf_counter()
        for i in idxs:
            if traces[i] is not None:
                traces[i].add_span("batch_wait", t_batch, now)

    def _template_scans(
        self, shape: plan_ir.PlanShape
    ) -> tuple[Relation, ...]:
        """Abstract (shape/dtype) scan templates for AOT-lowering a shape
        without staging device data — the only template source that is
        correct for PADDED shapes, whose scan caps exceed every member
        query's natural staging capacities."""
        sds = jax.ShapeDtypeStruct
        return tuple(
            Relation(
                schema,
                sds((cap, len(schema)), jnp.int32),
                sds((cap,), jnp.bool_),
            )
            for schema, cap in zip(shape.scan_schemas, shape.scan_caps)
        )

    def _coalesce_groups(
        self, groups: "OrderedDict[plan_ir.PlanShape, list[int]]"
    ) -> "OrderedDict[plan_ir.PlanShape, tuple[list[int], int, int]]":
        """Cross-shape padded stacking: merge near-miss plan groups —
        identical PlanShapes except for pow-2 scan caps — into one padded
        group at the per-position MAX caps, so a mixed-shape batch still
        coalesces into few stacked dispatches. Padding rows carry
        valid=False, which every masked operator already treats as
        absent, so merged lanes decode exactly the rows their natural
        shape would have produced.

        Guards (a rejected bucket simply keeps its per-shape groups):
          * every member shape must be WARM — a padded group has no
            calibration story of its own, so the padded entry's join caps
            are derived as the elementwise max of the members' calibrated
            caps, which only exist once each member has run;
          * the cost guard: padding waste (padded/real scan-cell ratio
            minus 1) must stay ≤ pad_waste_limit, so one huge outlier
            shape cannot inflate every lane's scan buffers;
          * the padded entry must compile (template lowering) — any
            failure falls back to per-shape groups rather than the
            sequential path.
        """
        buckets: OrderedDict[tuple, list[plan_ir.PlanShape]] = OrderedDict()
        for shape in groups:
            key = dataclasses.replace(
                shape, scan_caps=(0,) * len(shape.scan_caps)
            )
            buckets.setdefault(key, []).append(shape)
        merged: OrderedDict[
            plan_ir.PlanShape, tuple[list[int], int, int]
        ] = OrderedDict()
        for members in buckets.values():
            if len(members) < 2:
                s = members[0]
                merged[s] = (groups[s], 1, 0)
                continue
            entries = [self.plan_cache.get(s) for s in members]
            target = tuple(
                max(s.scan_caps[j] for s in members)
                for j in range(len(members[0].scan_caps))
            )
            n_q = sum(len(groups[s]) for s in members)
            real = sum(
                len(groups[s]) * sum(s.scan_caps) for s in members
            )
            padded = n_q * sum(target)
            ok = all(e is not None for e in entries)
            if ok and (padded - real) / real > self.pad_waste_limit:
                self.pad_rejects += 1
                ok = False
            n_compiles = 0
            padded_shape = None
            if ok:
                padded_shape = dataclasses.replace(
                    members[0], scan_caps=target
                )
                if self.plan_cache.get(padded_shape) is None:
                    join_caps = tuple(
                        max(e.join_caps[j] for e in entries)
                        for j in range(len(entries[0].join_caps))
                    )
                    # join caps are the elementwise max of caps that
                    # already compiled, so no capacity error is expected:
                    # a compile or device error propagates
                    sink = ExecStats()
                    self._compile_entry(
                        padded_shape, join_caps,
                        self._template_scans(padded_shape), None, sink,
                    )
                    n_compiles = sink.n_compiles
            if not ok:
                for s in members:
                    merged[s] = (groups[s], 1, 0)
                continue
            idxs = sorted(
                i for s in members for i in groups[s]
            )  # arrival order across member groups
            merged[padded_shape] = (idxs, len(members), n_compiles)
            self.padded_groups += 1
            self.padded_cells += padded
            self.real_cells += real
        return merged

    # -- batched execution internals ---------------------------------------
    def _batch_context(self, prog: _Program) -> "_BatchCtx":
        with self.store.snapshot_lock():
            _, shape, inverse = self._canonicalize(prog)
            version = self.store.version
        return _BatchCtx(
            prog=prog, shape=shape, inverse=inverse, store_version=version
        )

    def _run_single(
        self, pq: PreparedQuery, group: BatchGroupStats, defer: bool = False,
        trace=None,
    ) -> "ResultSet | Exception | PendingDecode":
        """Sequential fallback inside run_batch: the normal per-query path,
        with its dispatch/compile counts folded into the group's. With
        `defer`, host decode is left pending for the decode stage."""
        try:
            pending = pq._run_pending(trace)
        except Exception as e:
            return e
        group.n_dispatches += pending.stats.n_dispatches
        group.n_compiles += pending.stats.n_compiles
        return pending if defer else pending.resolve()

    def _run_group(
        self,
        shape: plan_ir.PlanShape,
        idxs: list[int],
        ctxs: list["_BatchCtx | None"],
        prepared: list[PreparedQuery],
        out: list,
        defer: bool = False,
        n_shapes: int = 1,
        extra_compiles: int = 0,
        traces: "list | None" = None,
        t_batch: "float | None" = None,
    ) -> None:
        if traces is None:
            traces = [None] * len(out)
        group = BatchGroupStats(
            n_queries=len(idxs),
            padded=n_shapes > 1,
            n_shapes=n_shapes,
            n_compiles=extra_compiles,  # the padded entry's template compile
        )
        self.last_batch.append(group)
        pos = 0
        if self.plan_cache.get(shape) is None:
            # cold shape: the first query runs the normal path (calibration
            # or warmup compile), populating the cache the rest stack on
            group.cold = True
            self._note_batch_wait(traces, idxs[:1], t_batch)
            out[idxs[0]] = self._run_single(
                prepared[idxs[0]], group, defer, traces[idxs[0]]
            )
            pos = 1
        # chunk at the pow-2 floor of the lane cap: max_batch_width bounds
        # device memory per dispatch, so it must never round UP
        width_cap = plan_ir.floor_pow2(self.max_batch_width)
        while pos < len(idxs):
            chunk = idxs[pos:pos + width_cap]
            pos += len(chunk)
            if len(chunk) < 2 or self.plan_cache.get(shape) is None:
                for i in chunk:
                    self._note_batch_wait(traces, [i], t_batch)
                    out[i] = self._run_single(
                        prepared[i], group, defer, traces[i]
                    )
                continue
            self._note_batch_wait(traces, chunk, t_batch)
            try:
                self._run_chunk_stacked(
                    shape, chunk, ctxs, prepared, out, group, defer, traces
                )
            except MemoryError:
                # a lane's bucket regrow passed max_capacity: isolate it by
                # re-running the chunk's queries sequentially so only the
                # culprit raises (compile and device errors propagate)
                group.fallback = True
                self.stacked_fallbacks += 1
                for i in chunk:
                    out[i] = self._run_single(
                        prepared[i], group, defer, traces[i]
                    )

    def _run_chunk_stacked(
        self,
        shape: plan_ir.PlanShape,
        chunk: list[int],
        ctxs: list["_BatchCtx | None"],
        prepared: list[PreparedQuery],
        out: list,
        group: BatchGroupStats,
        defer: bool = False,
        traces: "list | None" = None,
    ) -> None:
        """ONE stacked dispatch for a chunk of warm same-shape queries.

        For a PADDED group (`shape` is the coalesced max-caps signature)
        every lane's scans are padded up to `shape.scan_caps` — padding
        rows are valid=False, so the lane computes exactly what its
        natural shape would have."""
        entry = self.plan_cache.get(shape)
        n = len(chunk)
        width = plan_ir.bucket_width(n, self.max_batch_width)
        # pad trailing lanes with lane 0's inputs; lane_active masks them
        lanes = [ctxs[i] for i in chunk] + [ctxs[chunk[0]]] * (width - n)
        # per scan position: if every lane scans the SAME pattern (e.g. a
        # batch differing only in FILTER constants) AND its staged buffer
        # already sits at the group's capacity, ship the device buffer
        # once and let vmap broadcast it (in_axes=None) instead of
        # staging W stacked copies
        scans_b: list[Relation] = []
        axes: list[int | None] = []
        timed = traces is not None and any(
            traces[i] is not None for i in chunk
        )
        with _Stage(self, timed) as stg:
            with stg.locked():  # one store version per chunk
                for j in range(len(shape.scan_schemas)):
                    cap = shape.scan_caps[j]
                    tps = tuple(c.prog.patterns[j] for c in lanes)
                    rel = None
                    if len({self.store._scan_key(tp) for tp in tps}) == 1:
                        rel = self.store.match_pattern_device(tps[0])
                    if rel is not None and rel.capacity == cap:
                        scans_b.append(Relation(
                            shape.scan_schemas[j], rel.cols, rel.valid
                        ))
                        axes.append(None)
                    else:
                        scans_b.append(Relation(
                            shape.scan_schemas[j],
                            *self.store.stacked_scan_device(tps, cap=cap),
                        ))
                        axes.append(0)
                staged_version = self.store.version
            scans_b = tuple(scans_b)
            scan_axes = tuple(axes)
            consts_i = jnp.asarray(
                np.stack([c.prog.consts_i for c in lanes]))
            consts_f = jnp.asarray(
                np.stack([c.prog.consts_f for c in lanes]))
            active = jnp.asarray(np.arange(width) < n)
            num_vals = self.store.numeric_values_device()
        # retroactive span intervals, fanned out to every lane trace after
        # the chunk succeeds (one device launch -> N lane spans correlated
        # by a shared dispatch_id)
        events: list[tuple[str, float, float, dict]] = [
            ("stage", stg.t0, stg.t1, stg.attrs())
        ]
        group.n_broadcast_scans += sum(1 for a in scan_axes if a is None)
        stats = ExecStats(
            n_joins=shape.n_joins(),
            cache_hits=1,
            batch_width=width,
            store_version=staged_version,
        )
        self.plan_cache.hits += n
        if entry.num_cap not in (0, int(num_vals.shape[-1])):
            # dictionary growth crossed a pow-2 boundary since the entry
            # compiled: recompile at the same join caps (shape unchanged).
            # Templates come from the SHAPE, not lane 0's natural staging
            # — for a padded group those differ.
            entry = self._compile_entry(
                shape, entry.join_caps, self._template_scans(shape), None,
                stats,
            )
        ovf_counts = [0] * shape.n_joins()
        try:
            while True:
                bexec = entry.batched.get((width, scan_axes))
                if bexec is None:
                    tc0 = time.perf_counter()
                    bexec = ex.compile_plan_batched(
                        entry.compiled.plan,
                        scans_b,
                        consts_i,
                        consts_f,
                        num_vals,
                        active,
                        use_kernel=self.use_kernel,
                        scan_axes=scan_axes,
                    )
                    events.append(
                        ("compile", tc0, time.perf_counter(), {}))
                    entry.batched[(width, scan_axes)] = bexec
                    stats.n_compiles += 1
                    self.plan_cache.compiles += 1
                stats.n_dispatches += 1
                t0 = time.perf_counter()
                with self._launch(bexec):
                    rel_b, totals_b, flags_b = bexec(
                        scans_b, consts_i, consts_f, num_vals, active
                    )
                with phase(self.tracer, "sync"):
                    flags_np = np.asarray(flags_b)  # the single host sync
                    t1 = self._device_tick(stats, t0)
                events.append(("dispatch", t0, t1, {}))
                if not flags_np.any():
                    break
                # some lane overflowed a bucket: grow each flagged join to
                # the worst lane's exact total, recompile, retry the chunk
                stats.n_retries += 1
                totals_np = np.asarray(totals_b)
                overflowed = [
                    bool(flags_np[:, j].any())
                    for j in range(flags_np.shape[1])
                ]
                for j, f in enumerate(overflowed):
                    ovf_counts[j] += int(f)
                new_caps = plan_ir.grow_join_caps(
                    entry.join_caps,
                    [int(totals_np[:, j].max())
                     for j in range(totals_np.shape[1])],
                    overflowed,
                )
                if max(new_caps) > self.max_capacity:
                    raise MemoryError(
                        f"join result exceeds {self.max_capacity}"
                    )
                entry = self._compile_entry(
                    shape, new_caps, self._template_scans(shape), None,
                    stats,
                )
        finally:
            # the group ledger counts every launch and compile, including
            # those of a chunk that then failed over to the sequential path
            group.n_dispatches += stats.n_dispatches
            group.n_compiles += stats.n_compiles
        # the serving counters only describe *successful* stacked service,
        # so queries_per_dispatch can never be skewed by a failed chunk
        group.widths = group.widths + (width,)
        self.stacked_dispatches += stats.n_dispatches
        self.batch_width_hist[width] = (
            self.batch_width_hist.get(width, 0) + stats.n_dispatches
        )
        self.stacked_queries += n
        caps = entry.compiled.plan.join_caps
        stats.peak_join_bucket = max(caps) if caps else 0
        stats.peak_capacity = entry.compiled.plan.max_capacity()
        stats.join_caps = tuple(caps)
        stats.join_overflows = tuple(ovf_counts)
        # per-lane exact totals (width, n_joins): each lane's analyze view
        # reports ITS actual rows, not the chunk's
        lane_totals = self._chunk_lane_totals(totals_b)
        self._emit_chunk_results(
            rel_b, chunk, ctxs, prepared, out, stats, defer,
            lane_totals=lane_totals, traces=traces, events=events,
        )

    def _chunk_lane_totals(self, totals_b) -> tuple[np.ndarray, np.ndarray]:
        """Stacked totals -> per-lane (global, worst-partition) actuals,
        each (width, n_joins). On the single-device engine they coincide;
        the sharded override sums/maxes away its shard axis."""
        t = np.asarray(totals_b)
        return t, t

    def _emit_chunk_results(
        self,
        rel_b: Relation,
        chunk: list[int],
        ctxs: list["_BatchCtx | None"],
        prepared: list[PreparedQuery],
        out: list,
        stats: ExecStats,
        defer: bool,
        lane_totals: "tuple | None" = None,
        traces: "list | None" = None,
        events: "list | None" = None,
    ) -> None:
        """Unstack a chunk's result: ONE device→host transfer shared by
        every lane (lazy — the first decode consumer pays it), then
        per-lane row decode under each query's own variable names, either
        inline or left pending for the serving decode pool."""
        fetch = _SharedFetch(rel_b)
        schema = rel_b.schema
        if events:
            self._dispatch_seq += 1
        for k, i in enumerate(chunk):
            names = tuple(ctxs[i].inverse[v] for v in schema)
            st = dataclasses.replace(stats)
            if lane_totals is not None:
                totals, worst = lane_totals
                st.join_totals = tuple(int(x) for x in totals[k])
                st.join_worst = tuple(int(x) for x in worst[k])
                # the chunk's dispatch wall is shared: attribute an equal
                # share to each lane so the engine-level device_time_s
                # stays equal to the sum over per-run ExecStats
                st.device_time_s = stats.device_time_s / len(chunk)
            trace = traces[i] if traces is not None else None
            if trace is not None and events:
                for name, t0, t1, attrs in events:
                    trace.add_span(
                        name, t0, t1,
                        dispatch_id=self._dispatch_seq,
                        width=stats.batch_width, stacked=True, lane=k,
                        **attrs,
                    )
            pending = PendingDecode(
                self, prepared[i], names, names, fetch, k, st, trace,
            )
            out[i] = pending if defer else pending.resolve()

    # -- planning ----------------------------------------------------------
    def _lower_expr(
        self,
        expr: algebra.FilterExpr,
        id_consts: list[int],
        f_consts: list[float],
    ) -> plan_ir.FilterExpr:
        """Algebra filter expression -> plan expression, allocating the
        runtime-constant slots its literal leaves reference."""
        if isinstance(expr, algebra.Compare):
            if isinstance(expr.rhs, algebra.Var):
                return ("cmp", expr.lhs, expr.op, "var", expr.rhs.name)
            if isinstance(expr.rhs, algebra.NumLit):
                idx = len(f_consts)
                f_consts.append(expr.rhs.value)
                return ("cmp", expr.lhs, expr.op, "num", idx)
            # TermLit: identity comparison; unknown terms can never match
            # a bound variable, -1 encodes that correctly
            tid = self.store.dictionary.lookup(expr.rhs.lexical)
            idx = len(id_consts)
            id_consts.append(-1 if tid is None else tid)
            return ("cmp", expr.lhs, expr.op, "id", idx)
        tag = "and" if isinstance(expr, algebra.And) else "or"
        return (
            tag,
            tuple(
                self._lower_expr(c, id_consts, f_consts)
                for c in expr.children
            ),
        )

    def _build_program(self, q: Query) -> _Program:
        # the sharded engine reports its mesh size so the join ordering
        # can weigh shuffle cost; single-device engines pass 1 (no-op)
        plan = optimizer.optimize(
            q, self.store, enabled=self.optimize,
            n_shards=getattr(self, "n_shards", 1),
        )
        patterns = list(plan.all_patterns())
        opt_groups = tuple(
            plan_ir.GroupSpec(len(g), plan.opt_cross_flags[i])
            for i, g in enumerate(plan.opt_groups)
        )
        union_groups = tuple(
            plan_ir.GroupSpec(len(b), plan.branch_cross_flags[i])
            for i, b in enumerate(plan.branches)
        )
        id_consts: list[int] = []
        f_consts: list[float] = []
        # a conjunct the optimizer distributed into several UNION branches
        # is lowered once and shares its constant slots across the copies
        lowered: dict[int, plan_ir.FilterExpr] = {}
        specs: list[plan_ir.FilterSpec] = []
        for stage, expr in plan.filters:
            key = id(expr)
            if key not in lowered:
                lowered[key] = self._lower_expr(expr, id_consts, f_consts)
            specs.append((stage, lowered[key]))
        n_consts = (len(id_consts), len(f_consts))
        has_slice = q.has_slice()
        if has_slice:
            limit = q.limit if q.limit is not None else _NO_LIMIT
            id_consts += [min(q.offset, _NO_LIMIT), min(limit, _NO_LIMIT)]
        return _Program(
            q,
            plan,
            patterns,
            plan.cross_flags,
            opt_groups,
            union_groups,
            plan.has_required,
            tuple(specs),
            n_consts,
            np.asarray(id_consts, np.int32),
            np.asarray(f_consts, np.float32),
            tuple(q.projection()),
            q.distinct,
            has_slice,
        )

    def _shape_for(
        self,
        prog: _Program,
        schemas: tuple[tuple[str, ...], ...],
        caps: tuple[int, ...],
        rename: dict[str, str] | None = None,
    ) -> plan_ir.PlanShape:
        r = rename or {}

        def rn(v: str) -> str:
            return r.get(v, v)

        specs = tuple(
            (stage, plan_ir.rename_expr(expr, r))
            for stage, expr in prog.filters
        )
        # per-slot physical algebra rides in the shape (a backend flip is
        # a different compiled program); an engine-level override forces
        # every slot, otherwise the optimizer's per-node choice stands
        backends = prog.plan.join_backends
        if self.join_backend is not None:
            backends = (self.join_backend,) * len(backends)
        return plan_ir.make_shape(
            tuple(tuple(rn(v) for v in s) for s in schemas),
            caps,
            prog.cross_flags,
            tuple(rn(v) for v in prog.projection),
            prog.distinct,
            opt_groups=prog.opt_groups,
            union_groups=prog.union_groups,
            has_required=prog.has_required,
            filters=specs,
            n_consts=prog.n_consts,
            has_slice=prog.has_slice,
            prune=prog.plan.prune,
            join_backends=backends,
            scan_parts=self._scan_parts(prog, schemas),
        )

    def _scan_parts(
        self,
        prog: _Program,
        schemas: tuple[tuple[str, ...], ...],
    ) -> tuple[int, ...]:
        """Per-scan partition column (index into the scan's schema; -1 =
        unpartitioned). The single-device store is one shard, so nothing
        is partitioned; the sharded engine overrides with the store's
        subject-hash placement. Column positions are invariant under the
        canonical rename, so the shape stays structurally hashable."""
        return ()

    # -- execution ---------------------------------------------------------
    def _execute_program(
        self, prog: _Program, stats: ExecStats, trace=None
    ) -> Relation:
        if self.compiled:
            return self._execute_compiled(prog, stats, trace)
        with _Stage(self, trace is not None) as stg, stg.locked():
            # one consistent store version across the scans
            scans = tuple(
                self.store.match_pattern(tp) for tp in prog.patterns
            )
            stats.store_version = self.store.version
        if trace is not None:
            trace.add_span("stage", stg.t0, stg.t1, **stg.attrs())
        shape = self._shape_for(
            prog,
            tuple(s.schema for s in scans),
            tuple(s.capacity for s in scans),
        )
        t0 = time.perf_counter()
        rel, totals = self._eval_shape_eager(shape, scans, prog, stats)
        stats.join_totals = tuple(totals)
        stats.join_worst = stats.join_totals
        if trace is not None:
            trace.add_span("dispatch", t0, time.perf_counter(), eager=True)
        return rel

    def _decode_rows(self, rel: Relation) -> list[dict[str, str]]:
        return self._decode_numpy(rel.schema, rel.to_numpy())

    def _decode_numpy(
        self, schema: tuple[str, ...], rows: np.ndarray
    ) -> list[dict[str, str]]:
        d = self.store.dictionary
        return [
            {
                v: d.decode(int(t))
                for v, t in zip(schema, row)
                if int(t) != UNBOUND
            }
            for row in rows
        ]

    # -- eager evaluator ---------------------------------------------------
    def _eval_shape_eager(
        self,
        shape: plan_ir.PlanShape,
        scans: tuple[Relation, ...],
        prog: _Program,
        stats: ExecStats,
    ) -> tuple[Relation, list[int]]:
        """Operator-at-a-time evaluation with exact (count-pass) bucket
        sizing. Returns the result and each join's exact total in the same
        order the compiled program reports them — the totals are what the
        compiled path calibrates its buckets on, so filter stages must be
        applied at exactly the positions build_plan interleaves them."""
        totals: list[int] = []
        consts_i = jnp.asarray(prog.consts_i)
        consts_f = jnp.asarray(prog.consts_f)
        num_vals = self.store.numeric_values_device()
        by_stage: dict[tuple, list[plan_ir.FilterExpr]] = {}
        for stage, expr in shape.filters:
            by_stage.setdefault(stage, []).append(expr)

        def apply_stage(rel: Relation, stage: tuple) -> Relation:
            exprs = by_stage.get(stage)
            if not exprs:
                return rel
            keep = mj.filter_mask(
                rel, tuple(exprs), consts_i, consts_f, num_vals
            )
            return Relation(rel.schema, rel.cols, keep)

        scan_idx = 0

        def next_scan() -> Relation:
            nonlocal scan_idx
            rel = apply_stage(scans[scan_idx], ("scan", scan_idx))
            scan_idx += 1
            return rel

        def chain(
            n_scans: int,
            cross_flags: tuple[bool, ...],
            req_stages: bool = False,
        ) -> Relation:
            acc = next_scan()
            for j, is_cross in enumerate(cross_flags):
                acc, total = self._join_once(
                    acc, next_scan(), is_cross, stats
                )
                totals.append(total)
                if req_stages:
                    acc = apply_stage(acc, ("req", j))
            return acc

        acc: Relation | None = None
        if shape.has_required:
            acc = chain(
                shape.n_required, shape.cross_flags, req_stages=True
            )
        for gi, g in enumerate(shape.opt_groups):
            grp = chain(g.n_scans, g.cross_flags)
            stats.n_joins += 1
            stats.n_dispatches += 1
            t0 = time.perf_counter()
            total = int(self._jit_count(acc, grp))
            self._device_tick(stats, t0)
            stats.n_count_passes += 1
            cap = max(1, _next_pow2(total))
            stats.n_dispatches += 1
            t0 = time.perf_counter()
            out, _, overflow = self._jit_left_join(
                acc, grp, capacity=cap, use_kernel=self.use_kernel
            )
            ok = not bool(overflow)
            self._device_tick(stats, t0)
            assert ok
            stats.peak_capacity = max(
                stats.peak_capacity, cap + acc.capacity
            )
            stats.peak_join_bucket = max(stats.peak_join_bucket, cap)
            totals.append(total)
            acc = apply_stage(out, ("opt", gi))
        if shape.union_groups:
            children: list[Relation] = []
            for bi, g in enumerate(shape.union_groups):
                branch = chain(g.n_scans, g.cross_flags)
                if acc is not None:
                    shared = [v for v in acc.schema if v in branch.schema]
                    branch, total = self._join_once(
                        acc, branch, not shared, stats
                    )
                    totals.append(total)
                children.append(apply_stage(branch, ("bjoin", bi)))
            schema: list[str] = []
            for c in children:
                for v in c.schema:
                    if v not in schema:
                        schema.append(v)
            acc = mj.union_all(children, tuple(schema))
        acc = apply_stage(acc, ("top",))
        acc = acc.project(list(shape.projection))
        if shape.distinct:
            acc = mj.distinct(acc)  # device-side dedup before decode
        if shape.has_slice:
            oi, li = shape.slice_const_indices()
            acc = mj.slice_valid(
                acc, int(prog.consts_i[oi]), int(prog.consts_i[li])
            )
        return acc, totals

    def _join_once(
        self, left: Relation, right: Relation, is_cross: bool, stats: ExecStats
    ) -> tuple[Relation, int]:
        # every branch ends in a host sync (int()/bool() of a device
        # scalar), so the _device_tick interval covers dispatch + sync —
        # the same accounting the compiled paths use
        stats.n_joins += 1
        if is_cross:
            cap = max(1, _next_pow2(left.capacity * right.capacity))
            stats.n_dispatches += 1
            t0 = time.perf_counter()
            out, total, overflow = self._jit_cross(left, right, capacity=cap)
            ok, total = not bool(overflow), int(total)
            self._device_tick(stats, t0)
            assert ok
            stats.peak_capacity = max(stats.peak_capacity, cap)
            stats.peak_join_bucket = max(stats.peak_join_bucket, cap)
            return mj.compact(out), total
        if self.exact_count_pass:
            stats.n_dispatches += 1
            t0 = time.perf_counter()
            total = int(self._jit_count(left, right))
            self._device_tick(stats, t0)
            stats.n_count_passes += 1
            cap = max(1, _next_pow2(total))
            stats.n_dispatches += 1
            t0 = time.perf_counter()
            out, _, overflow = self._jit_join(
                left, right, capacity=cap, use_kernel=self.use_kernel
            )
            ok = not bool(overflow)
            self._device_tick(stats, t0)
            assert ok
            stats.peak_capacity = max(stats.peak_capacity, cap)
            stats.peak_join_bucket = max(stats.peak_join_bucket, cap)
            return out, total
        cap = max(left.capacity, right.capacity)
        while True:
            stats.n_dispatches += 1
            t0 = time.perf_counter()
            out, total, overflow = self._jit_join(
                left, right, capacity=cap, use_kernel=self.use_kernel
            )
            overflowed = bool(overflow)
            self._device_tick(stats, t0)
            stats.peak_capacity = max(stats.peak_capacity, cap)
            stats.peak_join_bucket = max(stats.peak_join_bucket, cap)
            if not overflowed:
                return out, int(total)
            stats.n_retries += 1
            cap *= 2
            if cap > self.max_capacity:
                raise MemoryError(f"join result exceeds {self.max_capacity}")

    # -- compiled path -----------------------------------------------------
    def _canonicalize(
        self, prog: _Program
    ) -> tuple[tuple[Relation, ...], plan_ir.PlanShape, dict[str, str]]:
        """Device scans + cache key for a program: upload-once scans
        (bucketed pow-2 capacities), variable names canonicalised so
        structurally-equal queries share one compiled program (constants
        live in the scan data and the runtime-constant inputs, not here).
        Returns (canonical scans, shape, canonical -> original names).

        Staging runs under the store's snapshot lock so every scan reflects
        ONE store version even while concurrent updates land."""
        with self.store.snapshot_lock():
            scans = tuple(
                self.store.match_pattern_device(tp) for tp in prog.patterns
            )
        schemas = tuple(s.schema for s in scans)
        rename = plan_ir.canonical_renaming(schemas)
        inverse = {c: o for o, c in rename.items()}
        canon_scans = tuple(
            Relation(tuple(rename[v] for v in s.schema), s.cols, s.valid)
            for s in scans
        )
        shape = self._shape_for(
            prog, schemas, self._scan_caps(scans), rename
        )
        return canon_scans, shape, inverse

    def _scan_caps(
        self, scans: tuple[Relation, ...]
    ) -> tuple[int, ...]:
        """Scan capacities as the PlanShape records them (the sharded
        engine overrides this to report PER-SHARD buckets)."""
        return tuple(s.capacity for s in scans)

    def _device_consts(
        self, prog: _Program
    ) -> tuple[jax.Array, jax.Array, jax.Array]:
        """Device placement of the runtime-constant inputs (the sharded
        engine overrides this to replicate them over its mesh)."""
        return (
            jnp.asarray(prog.consts_i),
            jnp.asarray(prog.consts_f),
            self.store.numeric_values_device(),
        )

    def _caps_from_totals(self, totals: list[int]) -> tuple[int, ...]:
        """Join bucket capacities from the calibration run's exact totals
        (the sharded engine overrides this to size PER-SHARD buckets)."""
        return tuple(plan_ir.bucket_capacity(t) for t in totals)

    def _execute_compiled(
        self, prog: _Program, stats: ExecStats, trace=None
    ) -> Relation:
        with _Stage(self, trace is not None) as stg:
            with stg.locked():
                canon_scans, shape, inverse = self._canonicalize(prog)
                stats.store_version = self.store.version
            stats.n_joins = shape.n_joins()
            consts_i, consts_f, num_vals = self._device_consts(prog)
        if trace is not None:
            trace.add_span("stage", stg.t0, stg.t1, **stg.attrs())

        entry = self.plan_cache.get(shape)
        if entry is not None and entry.num_cap not in (
            0,
            int(num_vals.shape[-1]),
        ):
            # dictionary growth crossed a pow-2 boundary since the entry
            # compiled (the numeric table is an input shape the executable
            # is specialised on): recompile at the same join caps
            entry = self._compile_entry(
                shape, entry.join_caps, canon_scans, prog, stats,
                trace=trace,
            )
        if entry is None:
            rel = self._compiled_cold(
                shape, canon_scans, prog, stats, trace
            )
        else:
            rel = self._compiled_warm(
                shape, entry, canon_scans, consts_i, consts_f, num_vals,
                stats, trace,
            )
        # back to the query's own variable names
        return Relation(
            tuple(inverse[v] for v in rel.schema), rel.cols, rel.valid
        )

    def _compiled_cold(
        self,
        shape: plan_ir.PlanShape,
        canon_scans: tuple[Relation, ...],
        prog: _Program,
        stats: ExecStats,
        trace=None,
    ) -> Relation:
        """Cache miss: the eager evaluator's count passes calibrate the join
        buckets; compile at those shapes; serve this query from the eager
        result (the compiled program takes over from the next query on).
        A shape with a saved warmup signature skips the calibration run and
        compiles straight at the persisted capacities."""
        stats.cache_misses += 1
        self.plan_cache.misses += 1
        warm_caps = self._warm_caps.get(shape)
        if warm_caps is not None and len(warm_caps) == shape.n_joins():
            entry = self._compile_entry(
                shape, warm_caps, canon_scans, prog, stats, trace=trace
            )
            return self._dispatch_entry(
                shape, entry, canon_scans, *self._device_consts(prog),
                stats, trace,
            )
        eager_stats = ExecStats()
        t0 = time.perf_counter()
        rel, totals = self._eval_shape_eager(
            shape, canon_scans, prog, eager_stats
        )
        if trace is not None:
            trace.add_span(
                "dispatch", t0, time.perf_counter(), calibration=True
            )
        stats.n_count_passes += eager_stats.n_count_passes
        stats.n_dispatches += eager_stats.n_dispatches
        stats.n_retries += eager_stats.n_retries
        stats.device_time_s += eager_stats.device_time_s
        stats.peak_capacity = max(
            stats.peak_capacity, eager_stats.peak_capacity
        )
        stats.peak_join_bucket = max(
            stats.peak_join_bucket, eager_stats.peak_join_bucket
        )
        join_caps = self._caps_from_totals(totals)
        stats.join_totals = tuple(totals)
        stats.join_worst = stats.join_totals
        stats.join_caps = join_caps
        self._compile_entry(
            shape, join_caps, canon_scans, prog, stats, trace=trace
        )
        return rel

    def _compiled_warm(
        self,
        shape: plan_ir.PlanShape,
        entry: PlanCacheEntry,
        canon_scans: tuple[Relation, ...],
        consts_i: jax.Array,
        consts_f: jax.Array,
        num_vals: jax.Array,
        stats: ExecStats,
        trace=None,
    ) -> Relation:
        stats.cache_hits += 1
        self.plan_cache.hits += 1
        return self._dispatch_entry(
            shape, entry, canon_scans, consts_i, consts_f, num_vals,
            stats, trace,
        )

    def _dispatch_entry(
        self,
        shape: plan_ir.PlanShape,
        entry: PlanCacheEntry,
        canon_scans: tuple[Relation, ...],
        consts_i: jax.Array,
        consts_f: jax.Array,
        num_vals: jax.Array,
        stats: ExecStats,
        trace=None,
    ) -> Relation:
        ovf_counts = [0] * shape.n_joins()
        while True:
            stats.n_dispatches += 1
            t0 = time.perf_counter()
            with self._launch(entry.compiled):
                rel, totals, flags = entry.compiled(
                    canon_scans, consts_i, consts_f, num_vals
                )
            stats.peak_capacity = max(
                stats.peak_capacity, entry.compiled.plan.max_capacity()
            )
            caps = entry.compiled.plan.join_caps
            stats.peak_join_bucket = max(
                stats.peak_join_bucket, max(caps) if caps else 0
            )
            with phase(self.tracer, "sync"):
                flags_np = np.asarray(flags)  # the single host sync
                t1 = self._device_tick(stats, t0)
                totals_np = np.asarray(totals)
            if trace is not None:
                trace.add_span("dispatch", t0, t1)
            if not flags_np.any():
                stats.join_totals = tuple(int(t) for t in totals_np)
                stats.join_worst = stats.join_totals
                stats.join_caps = tuple(caps)
                stats.join_overflows = tuple(ovf_counts)
                return rel
            # bucket overflow: grow from the exact totals, recompile, retry
            stats.n_retries += 1
            for j, f in enumerate(flags_np):
                ovf_counts[j] += int(bool(f))
            new_caps = plan_ir.grow_join_caps(
                entry.join_caps,
                [int(t) for t in totals_np],
                [bool(f) for f in flags_np],
            )
            if max(new_caps) > self.max_capacity:
                raise MemoryError(
                    f"join result exceeds {self.max_capacity}"
                )
            entry = self._compile_entry(
                shape, new_caps, canon_scans, None, stats, trace=trace
            )

    def _compile_entry(
        self,
        shape: plan_ir.PlanShape,
        join_caps: tuple[int, ...],
        canon_scans: tuple[Relation, ...],
        prog: _Program | None,
        stats: ExecStats,
        trace=None,
    ) -> PlanCacheEntry:
        t_compile = time.perf_counter()
        plan = plan_ir.build_plan(shape, join_caps)
        # the consts are signature templates here — only shapes/dtypes
        # matter to AOT lowering, and they are determined by the PlanShape
        n_i = shape.n_consts[0] + (2 if shape.has_slice else 0)
        n_f = shape.n_consts[1]
        consts_i = jnp.asarray(
            prog.consts_i if prog is not None else np.zeros(n_i, np.int32)
        )
        consts_f = jnp.asarray(
            prog.consts_f if prog is not None else np.zeros(n_f, np.float32)
        )
        compiled = ex.compile_plan(
            plan,
            canon_scans,
            consts_i,
            consts_f,
            self.store.numeric_values_device(),
            use_kernel=self.use_kernel,
        )
        stats.n_compiles += 1
        self.plan_cache.compiles += 1
        entry = PlanCacheEntry(
            shape,
            join_caps,
            compiled,
            warm_layouts=self._warm_layouts.get(shape, ()),
            num_cap=int(self.store.numeric_values_device().shape[-1]),
        )
        if prog is not None:
            # cold-compile path only: a regrow retry (prog=None) must not
            # pay vmap compiles for widths the next regrow would discard
            self._precompile_batched(entry, canon_scans, stats)
        self.plan_cache.put(shape, entry)
        if trace is not None:
            trace.add_span(
                "compile", t_compile, time.perf_counter(),
                n_joins=len(join_caps),
            )
        return entry

    def _precompile_batched(
        self,
        entry: PlanCacheEntry,
        canon_scans: tuple[Relation, ...],
        stats: ExecStats,
    ) -> None:
        """Compile stacked executables for the (width, scan-layout)
        signatures a previous process persisted (save_cache /
        warmup_path), so a restarted server's first micro-batch dispatches
        warm instead of paying the vmap compile. Abstract (shape/dtype)
        templates stand in for the batched inputs — no device data is
        staged here; broadcast scan positions keep their UNstacked
        template shapes."""
        width_cap = plan_ir.floor_pow2(self.max_batch_width)
        sds = jax.ShapeDtypeStruct
        for w, axes in entry.warm_layouts:
            key = (w, axes)
            if (
                key in entry.batched
                or w < 2
                or w > width_cap
                or len(axes) != len(canon_scans)
            ):
                continue
            scans_b = tuple(
                Relation(
                    s.schema,
                    sds(
                        ((w,) if ax == 0 else ()) + s.cols.shape,
                        s.cols.dtype,
                    ),
                    sds(
                        ((w,) if ax == 0 else ()) + s.valid.shape,
                        s.valid.dtype,
                    ),
                )
                for s, ax in zip(canon_scans, axes)
            )
            n_i = entry.shape.n_consts[0] + (
                2 if entry.shape.has_slice else 0
            )
            n_f = entry.shape.n_consts[1]
            entry.batched[key] = ex.compile_plan_batched(
                entry.compiled.plan,
                scans_b,
                sds((w, n_i), jnp.int32),
                sds((w, n_f), jnp.float32),
                self.store.numeric_values_device(),
                sds((w,), jnp.bool_),
                use_kernel=self.use_kernel,
                scan_axes=axes,
            )
            stats.n_compiles += 1
            self.plan_cache.compiles += 1

    # -- explain -----------------------------------------------------------
    def _explain_program(
        self, pq: PreparedQuery, prog: _Program, analyze: bool = False
    ) -> str:
        """Human-readable plan report: the logical algebra, the optimizer's
        pass-by-pass rewrite trace, the physical scan/join structure with
        estimated rows and pow-2 buckets, and the plan-cache state for
        this shape — all host-side (no device work). With `analyze`, the
        last run's per-join actuals (captured from the exact totals every
        dispatch returns) are appended beside the estimates."""
        est = self.store.estimate_cardinality
        lines = ["PreparedQuery", "logical algebra:"]
        lines.append(algebra.format_algebra(pq.query.algebra(), 1))
        lines.append(
            "optimizer trace (parse -> algebra -> optimize -> plan):"
        )
        for t in prog.plan.trace:
            lines.append(f"  {t}")
        lines.append("physical plan (scan order -> operator tree):")
        schemas: list[tuple[str, ...]] = []
        caps: list[int] = []
        n_req = len(prog.cross_flags) + 1 if prog.has_required else 0
        n_opt = sum(g.n_scans for g in prog.opt_groups)
        for i, tp in enumerate(prog.patterns):
            schema, _ = self.store.pattern_scan_info(tp)
            schemas.append(schema)
            caps.append(self.store.scan_capacity(tp))
            if i < n_req:
                kind = "required"
            elif i < n_req + n_opt:
                kind = "optional"
            else:
                kind = "union"
            lines.append(
                f"  scan[{i}] ({tp.s} {tp.p} {tp.o}) "
                f"est_rows={est(tp)} bucket={caps[-1]} [{kind}]"
            )
        rename = plan_ir.canonical_renaming(tuple(schemas))
        shape = self._shape_for(prog, tuple(schemas), tuple(caps), rename)
        ests = prog.plan.join_ests
        backends = shape.join_backends
        ji = 0

        def est_str() -> str:
            nonlocal ji
            out = (
                f" est_rows={int(ests[ji])}" if ji < len(ests) else ""
            )
            ji += 1
            return out

        def bk() -> str:
            """Physical algebra of the CURRENT join slot (pre-est_str),
            with the MR join's count method."""
            if ji < len(backends) and backends[ji] == "matrix":
                return "matrix_join"
            return f"mr_join count={mj.COUNT_METHOD}"

        for i, is_cross in enumerate(shape.cross_flags):
            kind = "cross_join" if is_cross else bk()
            lines.append(f"  join[{i}] {kind}{est_str()}")
        for gi, g in enumerate(shape.opt_groups):
            for _ in g.cross_flags:
                est_str()  # group-internal joins ride in the group line
            kind = bk()
            lines.append(
                f"  left_join[{gi}] ({kind}) OPTIONAL group of {g.n_scans} "
                f"pattern(s), unmatched rows padded UNBOUND,"
                f" inner{est_str()}"
            )
        for bi, g in enumerate(shape.union_groups):
            for _ in g.cross_flags:
                est_str()
            kind = bk()
            tail = est_str() if prog.has_required else ""
            lines.append(
                f"  union_branch[{bi}] {g.n_scans} pattern(s)"
                + (
                    f", joined with required chain ({kind}),{tail}"
                    if tail
                    else ""
                )
            )
        if shape.union_groups:
            lines.append(
                f"  union: concat {len(shape.union_groups)} branch(es), "
                "unbound columns padded UNBOUND"
            )
        for stage, expr in prog.plan.filters:
            lines.append(
                f"  filter: {expr} @ {optimizer._fmt_stage(stage)} "
                "(device-side mask)"
            )
        if shape.has_slice:
            q = pq.query
            limit = "-" if q.limit is None else q.limit
            lines.append(f"  slice: offset={q.offset} limit={limit}")
        entry = self.plan_cache.get(shape)
        if entry is None:
            lines.append(
                "cache: shape not compiled yet (first run calibrates "
                "buckets from exact counts, then compiles)"
            )
        else:
            lines.append(
                f"cache: compiled, join buckets={entry.join_caps}, "
                f"max_capacity={entry.compiled.plan.max_capacity()}"
            )
        lines.append(
            f"plan-cache: {len(self.plan_cache)} entries, "
            f"hit_rate={self.plan_cache.hit_rate:.0%}"
        )
        stale = pq.planned_version != self.store.version
        lines.append(
            f"store: version={self.store.version}, planned against "
            f"v{pq.planned_version}"
            + (
                " (stale: refresh() re-plans on current statistics; "
                "runs are snapshot-consistent either way)"
                if stale
                else ""
            )
        )
        lines.append(
            f"handle: {pq.n_runs} run(s)"
            + (
                f", last run: {pq.last_stats.n_dispatches} dispatch(es), "
                f"{pq.last_stats.n_compiles} compile(s)"
                if pq.last_stats
                else ""
            )
        )
        if analyze:
            lines.extend(self._analyze_lines(pq, prog, shape))
        return "\n".join(lines)

    # -- EXPLAIN ANALYZE ---------------------------------------------------
    def _join_slot_labels(
        self, shape: plan_ir.PlanShape, st: ExecStats
    ) -> list[str]:
        """Physical operator label per join slot, in the evaluation
        (totals) order — recovered from the plan tree by the same
        traversal the lowering uses, so labels line up with actuals."""
        n = len(st.join_totals)
        caps = st.join_caps if len(st.join_caps) == n else (0,) * n
        try:
            plan = plan_ir.build_plan(shape, tuple(caps))
            nodes = ex.join_slot_nodes(plan)
        except Exception:
            nodes = []
        labels = []
        for i in range(n):
            if i < len(nodes):
                node = nodes[i]
                kind = {
                    plan_ir.MRJoin: "mr_join",
                    plan_ir.MatrixJoin: "matrix_join",
                    plan_ir.CrossJoin: "cross_join",
                }.get(type(node))
                if kind is None and isinstance(node, plan_ir.LeftJoin):
                    kind = f"left_join[{node.backend}]"
                labels.append(kind or type(node).__name__.lower())
            else:
                labels.append("join")
        return labels

    def _analyze_slot_extra(self, st: ExecStats, i: int) -> str:
        """Per-slot suffix hook (the sharded engine adds worst-shard and
        shuffle pressure here)."""
        return ""

    def _analyze_tail(self, st: ExecStats) -> list[str]:
        """Run-summary hook after the per-slot lines."""
        return []

    def _analyze_lines(
        self, pq: PreparedQuery, prog: _Program, shape: plan_ir.PlanShape
    ) -> list[str]:
        st = pq.last_stats
        lines = ["EXPLAIN ANALYZE (last run):"]
        if st is None:
            lines.append("  no recorded run — execute the query first")
            return lines
        ests = prog.plan.join_ests
        if st.join_totals:
            labels = self._join_slot_labels(shape, st)
            for i, actual in enumerate(st.join_totals):
                est_v = int(ests[i]) if i < len(ests) else 0
                parts = [
                    f"  join[{i}] {labels[i]}",
                    f"est_rows={est_v}",
                    f"actual_rows={actual}",
                    f"q_error={optimizer.q_error(est_v, actual):.2f}",
                ]
                if i < len(st.join_caps):
                    cap = st.join_caps[i]
                    worst = (
                        st.join_worst[i]
                        if i < len(st.join_worst) else actual
                    )
                    parts.append(f"cap={cap}")
                    parts.append(
                        f"fill={worst / cap:.0%}" if cap else "fill=-"
                    )
                if i < len(st.join_overflows) and st.join_overflows[i]:
                    parts.append(f"overflows={st.join_overflows[i]}")
                lines.append(" ".join(parts) + self._analyze_slot_extra(st, i))
        elif st.n_joins:
            lines.append(
                "  actuals not captured for the last run "
                "(pre-observability execution path)"
            )
        else:
            lines.append("  no join nodes in this plan")
        lines.extend(self._analyze_tail(st))
        rows = st.rows_emitted if st.rows_emitted >= 0 else "-"
        lines.append(
            f"  run: {st.n_dispatches} dispatch(es), "
            f"{st.n_compiles} compile(s), {st.n_retries} retried, "
            f"batch_width={st.batch_width}, "
            f"device_time={st.device_time_s * 1e3:.2f}ms, "
            f"rows_emitted={rows}, store_version={st.store_version}"
        )
        return lines


@dataclasses.dataclass
class ShardedQueryEngine(QueryEngine):
    """Distributed MapSQ: the same engine over a subject-hash sharded store.

    `store` must be a sparql.sharded_store.ShardedTripleStore whose shard
    count equals the mesh size. Parsing, the algebra, the cost-based
    optimizer, the plan IR and the plan/compile cache are the single-device
    layers UNCHANGED; only three things differ:

      * scans come up as flat per-shard partitions (upload-once per shard)
        and the PlanShape's scan/join capacities are PER-SHARD buckets;
      * the compiled executable is core/dist_executor.py's one
        shard_map-wrapped dispatch — PARTITIONING-AWARE: a join input
        already hash-partitioned on the join key (subject-variable scans
        start that way) joins map-side with NO collective, a small
        misaligned side is broadcast (all_gather) instead of shuffling
        both, and only genuinely misaligned sides pay the hash shuffle;
        shuffles whose inputs are collective-free are issued ahead of the
        join chain so the interconnect overlaps the local joins;
      * overflow handling grows the worst SHARD's flagged bucket (join or
        shuffle — per mesh-axis stage) from the exact numbers that ride
        back with the dispatch, recompiles, and retries — the
        single-device discipline per shard.

    `mesh=None` builds a 1-axis mesh over every local device. Warm queries
    are exactly one dispatch and zero compiles, same as the base engine.
    """

    mesh: "jax.sharding.Mesh | None" = None
    axis_name: str = "shards"

    def __post_init__(self):
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from repro.sparql.sharded_store import ShardedTripleStore

        # the distributed executor lowers both local-join algebras (MR and
        # masked-SpMM matrix), so the optimizer's per-slot backend picks —
        # and an engine-level override — pass straight through: shard-local
        # joins after a shuffle/elision are ordinary joins
        if self.mesh is None:
            self.mesh = compat.make_mesh(
                (jax.device_count(),), (self.axis_name,)
            )
        self.axis_names = tuple(self.mesh.axis_names)
        self.n_shards = 1
        for a in self.axis_names:
            self.n_shards *= self.mesh.shape[a]
        if not isinstance(self.store, ShardedTripleStore):
            raise TypeError(
                "ShardedQueryEngine needs a ShardedTripleStore "
                f"(got {type(self.store).__name__}); wrap a TripleStore "
                "with sparql.sharded_store.shard_store(store, n_shards)"
            )
        if self.store.n_shards != self.n_shards:
            raise ValueError(
                f"store has {self.store.n_shards} shards but the mesh has "
                f"{self.n_shards} devices"
            )
        if not self.compiled:
            raise ValueError(
                "sharded execution is compiled-only (compiled=True)"
            )
        super().__post_init__()
        # cross-shape padded stacking is single-device only: the sharded
        # stacked path lowers through shard_map with concrete row-sharded
        # scan buffers, which the padded entry's abstract-template compile
        # cannot reproduce — near-miss shapes stay per-shape groups here
        self.pad_stacking = False
        self._row_sharding = NamedSharding(self.mesh, P(self.axis_names))
        self._rep_sharding = NamedSharding(self.mesh, P())
        self.store.row_sharding = self._row_sharding
        self._num_vals_rep = None
        self._num_vals_src = None  # store table the replica was built from
        # shuffle bucket signatures persisted by a previous process (the
        # sharded extension of the warmup file; absent in older files)
        self._warm_shuffle: dict[plan_ir.PlanShape, tuple[int, ...]] = {}
        if self.warmup_path is not None:
            p = pathlib.Path(self.warmup_path)
            if p.exists():
                for e in json.loads(p.read_text())["entries"]:
                    sh = tuple(int(c) for c in e.get("shuffle_caps", ()))
                    if sh:
                        shape = plan_ir.shape_from_jsonable(e["shape"])
                        self._warm_shuffle[shape] = sh

    # -- device placement --------------------------------------------------
    def _replicated(self, arr) -> jax.Array:
        return jax.device_put(arr, self._rep_sharding)

    def _num_vals(self) -> jax.Array:
        # the store rebuilds its table when inserts grow the dictionary;
        # rebuild the mesh replica whenever the source array changes (an
        # identity check — the store caches one array object per build)
        base = self.store.numeric_values_device()
        if self._num_vals_rep is None or self._num_vals_src is not base:
            self._num_vals_src = base
            self._num_vals_rep = self._replicated(np.asarray(base))
        return self._num_vals_rep

    def _device_consts(self, prog: _Program):
        return (
            self._replicated(prog.consts_i),
            self._replicated(prog.consts_f),
            self._num_vals(),
        )

    # -- planning ----------------------------------------------------------
    def _scan_caps(
        self, scans: tuple[Relation, ...]
    ) -> tuple[int, ...]:
        """Capacities entering the PlanShape are the PER-SHARD row
        buckets (the flat scan buffer holds n_shards equal blocks, so
        its per-shard slice is capacity // n_shards)."""
        return tuple(s.capacity // self.n_shards for s in scans)

    def _scan_parts(
        self,
        prog: _Program,
        schemas: tuple[tuple[str, ...], ...],
    ) -> tuple[int, ...]:
        """The store shards rows by subject hash — the SAME FNV-1a route
        the shuffle uses — so a subject-VARIABLE scan arrives already
        hash-partitioned on that column; the lowering elides every
        shuffle this placement satisfies. A constant subject pins all
        matches to one shard (not a hash placement of any variable)."""
        return tuple(
            schema.index(tp.s) if tp.s.startswith("?") else -1
            for tp, schema in zip(prog.patterns, schemas)
        )

    def _axis_sizes(self) -> tuple[int, ...]:
        return tuple(self.mesh.shape[a] for a in self.axis_names)

    def _caps_from_totals(self, totals: list[int]) -> tuple[int, ...]:
        """Per-shard join buckets from the calibration run's exact GLOBAL
        totals: the uniform-hash share, pow-2 bucketed. Key skew shows up
        as an overflow on the first dispatch and regrows from the worst
        shard's exact total."""
        return tuple(
            plan_ir.bucket_capacity(max(1, -(-int(t) // self.n_shards)))
            for t in totals
        )

    # -- compiled path -----------------------------------------------------
    def _compiled_cold(
        self,
        shape: plan_ir.PlanShape,
        canon_scans: tuple[Relation, ...],
        prog: _Program,
        stats: ExecStats,
        trace=None,
    ) -> Relation:
        """Cache miss: calibrate GLOBAL join totals with the eager
        evaluator (the flat scan buffer is a valid single-device relation,
        so the count passes are exact), size per-shard buckets at the
        uniform-hash share, then DISPATCH once — unlike the base engine,
        the cold query is served from the mesh so any hash-skew overflow
        regrows now and warm queries stay at one dispatch, zero compiles."""
        stats.cache_misses += 1
        self.plan_cache.misses += 1
        warm_caps = self._warm_caps.get(shape)
        if warm_caps is not None and len(warm_caps) == shape.n_joins():
            entry = self._compile_entry(
                shape, warm_caps, canon_scans, prog, stats, trace=trace
            )
        else:
            eager_stats = ExecStats()
            t0 = time.perf_counter()
            _, totals = self._eval_shape_eager(
                shape, canon_scans, prog, eager_stats
            )
            if trace is not None:
                trace.add_span(
                    "dispatch", t0, time.perf_counter(), calibration=True
                )
            stats.n_count_passes += eager_stats.n_count_passes
            stats.n_dispatches += eager_stats.n_dispatches
            stats.n_retries += eager_stats.n_retries
            stats.device_time_s += eager_stats.device_time_s
            entry = self._compile_entry(
                shape, self._caps_from_totals(totals), canon_scans, prog,
                stats, trace=trace,
            )
        return self._dispatch_entry(
            shape, entry, canon_scans, *self._device_consts(prog), stats,
            trace,
        )

    def _compile_entry(
        self,
        shape: plan_ir.PlanShape,
        join_caps: tuple[int, ...],
        canon_scans: tuple[Relation, ...],
        prog: "_Program | None",
        stats: ExecStats,
        trace=None,
        shuffle_caps: "tuple[int, ...] | None" = None,
    ) -> PlanCacheEntry:
        from repro.core import dist_executor as dx

        t_compile = time.perf_counter()
        plan = plan_ir.build_plan(shape, join_caps)
        # one shuffle slot per site per mesh-axis stage (stages of a
        # hierarchical shuffle size and regrow independently); warmup
        # files from before the per-stage split carry the wrong length
        # and fall through to fresh estimates
        n_slots = dx.n_shuffle_slots(plan, len(self.axis_names))
        if shuffle_caps is None:
            prev = self.plan_cache.get(shape)
            if prev is not None and len(
                prev.compiled.shuffle_caps
            ) == n_slots:
                shuffle_caps = prev.compiled.shuffle_caps
            else:
                shuffle_caps = self._warm_shuffle.get(shape)
        if shuffle_caps is None or len(shuffle_caps) != n_slots:
            shuffle_caps = dx.initial_shuffle_caps(plan, self._axis_sizes())
        n_i = shape.n_consts[0] + (2 if shape.has_slice else 0)
        n_f = shape.n_consts[1]
        consts_i = self._replicated(
            prog.consts_i if prog is not None else np.zeros(n_i, np.int32)
        )
        consts_f = self._replicated(
            prog.consts_f if prog is not None else np.zeros(n_f, np.float32)
        )
        compiled = dx.compile_sharded_plan(
            plan,
            self.mesh,
            self.axis_names,
            shuffle_caps,
            canon_scans,
            consts_i,
            consts_f,
            self._num_vals(),
            use_kernel=self.use_kernel,
        )
        stats.n_compiles += 1
        self.plan_cache.compiles += 1
        entry = PlanCacheEntry(
            shape,
            join_caps,
            compiled,
            num_cap=int(self._num_vals().shape[-1]),
        )
        self.plan_cache.put(shape, entry)
        if trace is not None:
            trace.add_span(
                "compile", t_compile, time.perf_counter(),
                n_joins=len(join_caps), sharded=True,
            )
        return entry

    def _dispatch_entry(
        self,
        shape: plan_ir.PlanShape,
        entry: PlanCacheEntry,
        canon_scans: tuple[Relation, ...],
        consts_i: jax.Array,
        consts_f: jax.Array,
        num_vals: jax.Array,
        stats: ExecStats,
        trace=None,
    ) -> Relation:
        ovf_counts = [0] * shape.n_joins()
        while True:
            stats.n_dispatches += 1
            self._count_shuffles(entry, stats)
            t0 = time.perf_counter()
            with self._launch(entry.compiled):
                res = entry.compiled(
                    canon_scans, consts_i, consts_f, num_vals
                )
            caps = entry.compiled.plan.join_caps
            stats.peak_capacity = max(
                stats.peak_capacity, entry.compiled.plan.max_capacity()
            )
            stats.peak_join_bucket = max(
                stats.peak_join_bucket, max(caps) if caps else 0
            )
            with phase(self.tracer, "sync"):
                # the single host sync: join AND shuffle flags, all shards
                flags_np = np.asarray(res.overflows)
                sh_flags_np = np.asarray(res.shuffle_flags)
                t1 = self._device_tick(stats, t0)
            if trace is not None:
                trace.add_span(
                    "dispatch", t0, t1, n_shards=self.n_shards
                )
            if not flags_np.any() and not sh_flags_np.any():
                # totals are (n_shards, n_joins): the analyze view wants
                # the global rows AND the worst shard (fill pressure is a
                # per-shard property under hash skew)
                totals_np = np.asarray(res.totals)
                needs_np = np.asarray(res.shuffle_needs)
                stats.join_totals = tuple(
                    int(x) for x in totals_np.sum(axis=0)
                )
                stats.join_worst = tuple(
                    int(x) for x in totals_np.max(axis=0)
                )
                stats.join_caps = tuple(caps)
                stats.join_overflows = tuple(ovf_counts)
                if needs_np.size:
                    stats.shuffle_loads = tuple(
                        int(x) for x in needs_np.max(axis=0)
                    )
                return res.relation
            # a bucket overflowed on some shard: grow the flagged ones
            # from the worst shard's exact numbers, recompile, retry
            stats.n_retries += 1
            totals_np = np.asarray(res.totals)
            needs_np = np.asarray(res.shuffle_needs)
            n_j = flags_np.shape[1]
            n_s = sh_flags_np.shape[1]  # (site x mesh-axis stage) slots
            for j in range(n_j):
                ovf_counts[j] += int(bool(flags_np[:, j].any()))
            new_caps = plan_ir.grow_join_caps(
                entry.join_caps,
                [int(totals_np[:, j].max()) for j in range(n_j)],
                [bool(flags_np[:, j].any()) for j in range(n_j)],
            )
            new_shuffle = plan_ir.grow_join_caps(
                entry.compiled.shuffle_caps,
                [int(needs_np[:, j].max()) for j in range(n_s)],
                [bool(sh_flags_np[:, j].any()) for j in range(n_s)],
            )
            if max(new_caps + new_shuffle) > self.max_capacity:
                raise MemoryError(
                    f"join result exceeds {self.max_capacity}"
                )
            entry = self._compile_entry(
                shape, new_caps, canon_scans, None, stats, trace=trace,
                shuffle_caps=new_shuffle,
            )

    def _count_shuffles(self, entry: PlanCacheEntry, stats: ExecStats):
        """Fold the compiled program's static data-movement choices into
        the run's stats, once per mesh dispatch."""
        from repro.core import dist_executor as dx

        cnt = dx.strategy_counts(entry.compiled.strategies)
        stats.n_shuffles_emitted += cnt["emitted"]
        stats.n_shuffles_elided += cnt["elided"]
        stats.n_broadcast_joins += cnt["broadcast"]

    # -- batching ----------------------------------------------------------
    def _run_chunk_stacked(
        self,
        shape: plan_ir.PlanShape,
        chunk: list[int],
        ctxs: list["_BatchCtx | None"],
        prepared: list[PreparedQuery],
        out: list,
        group: BatchGroupStats,
        defer: bool = False,
        traces: "list | None" = None,
    ) -> None:
        """ONE stacked mesh dispatch (lanes x shards) for a chunk of warm
        same-shape queries — the distributed mirror of the base engine's
        stacked path: the per-shard program is vmapped over lanes inside
        shard_map, so a micro-batch's shuffles/joins for every lane ride
        one launch. Grouping, chunking, deferred decode and the
        sequential-fallback safety net are the inherited run_batch
        machinery (cross-shape padding stays disabled here, so `shape` is
        always every lane's natural signature)."""
        from repro.core import dist_executor as dx

        entry = self.plan_cache.get(shape)
        n = len(chunk)
        width = plan_ir.bucket_width(n, self.max_batch_width)
        lanes = [ctxs[i] for i in chunk] + [ctxs[chunk[0]]] * (width - n)
        # per scan position: identical pattern across lanes -> ship the
        # row-sharded buffer once (vmap broadcasts it); else a stacked
        # (width, n_shards * cap) buffer — the mesh splits rows (dim 1),
        # vmap splits lanes (dim 0)
        scans_b: list[Relation] = []
        axes: list[int | None] = []
        timed = traces is not None and any(
            traces[i] is not None for i in chunk
        )
        with _Stage(self, timed) as stg:
            with stg.locked():  # one store version per chunk
                for j in range(len(shape.scan_schemas)):
                    tps = tuple(c.prog.patterns[j] for c in lanes)
                    if len({self.store._scan_key(tp) for tp in tps}) == 1:
                        rel = self.store.match_pattern_device(tps[0])
                        scans_b.append(Relation(
                            shape.scan_schemas[j], rel.cols, rel.valid
                        ))
                        axes.append(None)
                    else:
                        scans_b.append(Relation(
                            shape.scan_schemas[j],
                            *self.store.stacked_scan_device(tps),
                        ))
                        axes.append(0)
                staged_version = self.store.version
            scans_b = tuple(scans_b)
            scan_axes = tuple(axes)
            consts_i = self._replicated(
                np.stack([c.prog.consts_i for c in lanes])
            )
            consts_f = self._replicated(
                np.stack([c.prog.consts_f for c in lanes])
            )
            active = self._replicated(np.arange(width) < n)
            num_vals = self._num_vals()
        events: list[tuple[str, float, float, dict]] = [
            ("stage", stg.t0, stg.t1, stg.attrs())
        ]
        group.n_broadcast_scans += sum(1 for a in scan_axes if a is None)
        stats = ExecStats(
            n_joins=shape.n_joins(),
            cache_hits=1,
            batch_width=width,
            store_version=staged_version,
        )
        self.plan_cache.hits += n
        if entry.num_cap not in (0, int(num_vals.shape[-1])):
            template_scans, _, _ = self._canonicalize(lanes[0].prog)
            entry = self._compile_entry(
                shape, entry.join_caps, template_scans, None, stats
            )
        ovf_counts = [0] * shape.n_joins()
        try:
            while True:
                bexec = entry.batched.get((width, scan_axes))
                if bexec is None:
                    tc0 = time.perf_counter()
                    bexec = dx.compile_sharded_plan_batched(
                        entry.compiled.plan,
                        self.mesh,
                        self.axis_names,
                        entry.compiled.shuffle_caps,
                        scans_b,
                        consts_i,
                        consts_f,
                        num_vals,
                        active,
                        scan_axes,
                        use_kernel=self.use_kernel,
                    )
                    events.append(
                        ("compile", tc0, time.perf_counter(), {}))
                    entry.batched[(width, scan_axes)] = bexec
                    stats.n_compiles += 1
                    self.plan_cache.compiles += 1
                stats.n_dispatches += 1
                self._count_shuffles(entry, stats)
                t0 = time.perf_counter()
                with self._launch(bexec):
                    res = bexec(
                        scans_b, consts_i, consts_f, num_vals, active
                    )
                with phase(self.tracer, "sync"):
                    # the single host sync: join AND shuffle flags, every
                    # (lane, shard) pair
                    flags_np = np.asarray(res.overflows)
                    sh_flags_np = np.asarray(res.shuffle_flags)
                    t1 = self._device_tick(stats, t0)
                events.append(("dispatch", t0, t1, {}))
                if not flags_np.any() and not sh_flags_np.any():
                    break
                # a bucket overflowed in some lane on some shard: grow the
                # flagged ones to the worst (lane, shard)'s exact numbers,
                # recompile (solo entry + this width), retry the chunk
                stats.n_retries += 1
                totals_np = np.asarray(res.totals)
                needs_np = np.asarray(res.shuffle_needs)
                n_j = flags_np.shape[-1]
                n_s = sh_flags_np.shape[-1]
                for j in range(n_j):
                    ovf_counts[j] += int(bool(flags_np[..., j].any()))
                new_caps = plan_ir.grow_join_caps(
                    entry.join_caps,
                    [int(totals_np[..., j].max()) for j in range(n_j)],
                    [bool(flags_np[..., j].any()) for j in range(n_j)],
                )
                new_shuffle = plan_ir.grow_join_caps(
                    entry.compiled.shuffle_caps,
                    [int(needs_np[..., j].max()) for j in range(n_s)],
                    [bool(sh_flags_np[..., j].any()) for j in range(n_s)],
                )
                if max(new_caps + new_shuffle) > self.max_capacity:
                    raise MemoryError(
                        f"join result exceeds {self.max_capacity}"
                    )
                template_scans, _, _ = self._canonicalize(lanes[0].prog)
                entry = self._compile_entry(
                    shape, new_caps, template_scans, None, stats,
                    shuffle_caps=new_shuffle,
                )
        finally:
            group.n_dispatches += stats.n_dispatches
            group.n_compiles += stats.n_compiles
        group.widths = group.widths + (width,)
        self.stacked_dispatches += stats.n_dispatches
        self.batch_width_hist[width] = (
            self.batch_width_hist.get(width, 0) + stats.n_dispatches
        )
        self.stacked_queries += n
        caps = entry.compiled.plan.join_caps
        stats.peak_join_bucket = max(caps) if caps else 0
        stats.peak_capacity = entry.compiled.plan.max_capacity()
        stats.join_caps = tuple(caps)
        stats.join_overflows = tuple(ovf_counts)
        needs_np = np.asarray(res.shuffle_needs)
        if needs_np.size:
            # (width, n_shards, n_slots) -> worst shard over every lane
            stats.shuffle_loads = tuple(
                int(x) for x in needs_np.max(axis=(0, 1))
            )
        self._emit_chunk_results(
            res.relation, chunk, ctxs, prepared, out, stats, defer,
            lane_totals=self._chunk_lane_totals(res.totals),
            traces=traces, events=events,
        )

    def _chunk_lane_totals(self, totals_b) -> tuple[np.ndarray, np.ndarray]:
        # batched sharded totals are (width, n_shards, n_joins): per-lane
        # global rows sum over shards, fill pressure is the worst shard
        t = np.asarray(totals_b)
        return t.sum(axis=1), t.max(axis=1)

    # -- persistence -------------------------------------------------------
    def _entry_jsonable(self, e: PlanCacheEntry) -> dict:
        """Base signature plus the entry's shuffle bucket caps, so a
        restarted sharded server compiles warm shapes with zero
        shuffle-overflow retries too."""
        d = super()._entry_jsonable(e)
        d["shuffle_caps"] = list(e.compiled.shuffle_caps)
        return d

    # -- explain -----------------------------------------------------------
    def _analyze_slot_extra(self, st: ExecStats, i: int) -> str:
        if i < len(st.join_worst):
            return f" worst_shard_rows={st.join_worst[i]}"
        return ""

    def _analyze_tail(self, st: ExecStats) -> list[str]:
        lines = []
        if st.shuffle_loads:
            lines.append(
                "  shuffle slots worst-shard rows="
                f"{list(st.shuffle_loads)}"
            )
        lines.append(
            f"  data movement: {st.n_shuffles_emitted} shuffle(s) "
            f"emitted, {st.n_shuffles_elided} elided, "
            f"{st.n_broadcast_joins} broadcast join(s)"
        )
        return lines

    def _explain_program(
        self, pq: PreparedQuery, prog: _Program, analyze: bool = False
    ) -> str:
        lines = [super()._explain_program(pq, prog, analyze=analyze)]
        lines.append(
            f"sharded: {self.n_shards} shard(s), mesh axes "
            f"{list(self.axis_names)}, subject-hash partitioned scans"
        )
        schemas: list[tuple[str, ...]] = []
        caps: list[int] = []
        for i, tp in enumerate(prog.patterns):
            counts = self.store.per_shard_counts(tp)
            schema, _ = self.store.pattern_scan_info(tp)
            schemas.append(schema)
            caps.append(self.store.scan_capacity(tp))
            lines.append(
                f"  scan[{i}] per-shard rows={counts} "
                f"per-shard bucket={caps[-1]}"
            )
        rename = plan_ir.canonical_renaming(tuple(schemas))
        shape = self._shape_for(prog, tuple(schemas), tuple(caps), rename)
        entry = self.plan_cache.get(shape)
        if entry is not None:
            lines.append(
                f"  per-shard join buckets={entry.join_caps}, "
                f"shuffle buckets={entry.compiled.shuffle_caps}"
            )
            strategies = entry.compiled.strategies
        else:
            # not compiled yet: derive the strategies the lowering WILL
            # choose (pure static analysis over the would-be plan)
            from repro.core import dist_executor as dx

            plan = plan_ir.build_plan(
                shape, (plan_ir.MIN_BUCKET,) * shape.n_joins()
            )
            strategies = dx.analyze_plan(plan, self.n_shards)
        from repro.core import dist_executor as dx

        for i, st in enumerate(strategies):
            lines.append(f"  shuffle[{i}] {st.op}: {dx.format_strategy(st)}")
        cnt = dx.strategy_counts(strategies)
        lines.append(
            f"  shuffles: {cnt['emitted']} emitted, {cnt['elided']} "
            f"elided, {cnt['broadcast']} broadcast join(s)"
        )
        return "\n".join(lines)
