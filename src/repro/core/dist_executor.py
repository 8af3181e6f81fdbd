"""Distributed executor: one shard_map dispatch for the whole plan tree.

`core/executor.py` lowers a PhysicalPlan to a single-device program; this
module lowers the SAME plan IR to a mesh program, so the parser, algebra,
optimizer, plan-shape cache and bucket-calibration layers above stay
unchanged.

The lowering is PARTITIONING-AWARE (the cascading map-side-join idea):
`analyze_plan` propagates a `Partitioning` property bottom-up — a
subject-variable Scan of the subject-hash sharded store starts hash-
partitioned on its subject column (the store routes by the SAME FNV-1a
hash `shuffle_by_key` routes by, so "partitioned on ?s" and "shuffled by
(?s,)" are the same physical placement), each join computes its output
partitioning, and a shuffle collective is emitted ONLY when an input's
partitioning does not already match the join key. A subject-subject star
join chain therefore runs with ZERO collectives: every step is a pure
map-side join. Inside the one `shard_map`-wrapped dispatch:

  * Scan    — reads the shard-local partition of the sharded store's flat
              (n_shards * cap) scan buffer (the in_spec splits on exactly
              the per-shard row blocks the store laid out); partitioned on
              its subject column when the subject is a variable;
  * MRJoin / MatrixJoin — per side: already aligned -> local (no
              collective); small right side -> all_gather it and keep the
              big left side in place (one-sided broadcast join);
              otherwise the paper's Map phase: a hash shuffle over the
              mesh (core/distributed.shuffle_by_key) — then each shard
              runs the local Algorithm-1 join (or the masked-SpMM matrix
              backend, which composes with elision unchanged);
  * LeftJoin— same strategy menu (only the RIGHT side may broadcast:
              unmatched-left padding is emitted per shard, so the left
              side must stay uniquely placed); unmatched-left padding is
              globally correct because every left row meets ALL right
              rows of its key;
  * CrossJoin — the right side is all_gathered (replicated) and each
              shard crosses its local left slice against it;
  * Filter / Project / UnionAll — purely row-local; Project keeps the
              partitioning property when the partition columns survive;
  * Distinct — elides its co-locating shuffle when the child is already
              hash-partitioned on any subset of its columns (equal rows
              agree on every column, so they already share a shard);
              otherwise rows shuffle by a hash of ALL columns at a
              calibrated per-shard bucket;
  * Slice   — LIMIT/OFFSET against the GLOBAL valid-row rank.

OVERLAP: before the join chain runs, every emitted shuffle whose input is
a collective-free subtree (scan/filter/project) is issued into a
`distributed.ShuffleSlots` double buffer. Those all_to_alls carry no data
dependency on earlier joins, so in program order they all sit ahead of
the chain and XLA's async collectives can run the shuffle for join k+1
while join k's local compute is still going.

Everything dynamic rides back in the same dispatch, per shard: exact join
totals, join-bucket overflow flags, exact shuffle bucket needs and
overflow flags — PER SITE AND PER MESH-AXIS STAGE, so an overflow regrows
only the overflowing stage's bucket (a skewed pod-stage load no longer
inflates the chip-stage buffers). Static shapes are all PER-SHARD, which
is what makes the memory footprint scale down with the mesh.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import compat
from repro.core import distributed as dj
from repro.core import executor as ex
from repro.core import matrix_join as mxj
from repro.core import mr_join as mj
from repro.core.plan_ir import (
    CrossJoin,
    Distinct,
    Filter,
    LeftJoin,
    MatrixJoin,
    MRJoin,
    PhysicalPlan,
    PlanNode,
    Project,
    Scan,
    Slice,
    UnionAll,
    child_nodes,
)
from repro.core.relation import Relation

# global-row threshold below which a misaligned join input is replicated
# (all_gather) instead of shuffling BOTH sides: one collective moving few
# rows, and the big side's partitioning survives the join
DEFAULT_BROADCAST_ROWS = 2048


class ShardedChainResult(NamedTuple):
    """Everything one sharded dispatch returns (device-resident).

    `relation` rows gather over shards (shard k's slice is row block k);
    the per-join and per-shuffle accounting keeps the shard axis so the
    host can regrow buckets from the worst shard's exact numbers. The
    shuffle arrays carry one slot per site PER MESH-AXIS STAGE
    (n_sites * n_stages, site-major), so a hierarchical shuffle's stages
    regrow independently.
    """

    relation: Relation  # rows sharded: (n_shards * cap_out, n_cols)
    totals: jax.Array  # (n_shards, n_joins) exact local join totals
    overflows: jax.Array  # (n_shards, n_joins) join bucket truncated
    shuffle_needs: jax.Array  # (n_shards, n_sites * n_stages) worst load
    shuffle_flags: jax.Array  # (n_shards, n_sites * n_stages) dropped


# -- partitioning property (the map-side-join lattice) ------------------------


@dataclasses.dataclass(frozen=True)
class Partitioning:
    """Where a relation's rows live across the mesh.

    hash(cols)  — the row with values v over `cols` lives on shard
                  FNV1a(v) % n_shards (column ORDER matters: the hash is
                  over the tuple in this order — exactly
                  distributed.hash_keys' routing);
    replicated  — every shard holds every row (an all_gather output);
    unknown     — arbitrary placement (the lattice bottom).
    """

    kind: str  # "hash" | "replicated" | "unknown"
    cols: tuple[str, ...] = ()

    def __str__(self) -> str:
        if self.kind == "hash":
            return "hash(" + ",".join(self.cols) + ")"
        return self.kind


UNKNOWN = Partitioning("unknown")
REPLICATED = Partitioning("replicated")


def hash_part(cols) -> Partitioning:
    cols = tuple(cols)
    assert cols
    return Partitioning("hash", cols)


@dataclasses.dataclass(frozen=True)
class SiteStrategy:
    """One shuffle site's chosen physical data movement.

    op: "mr_join" | "matrix_join" | "left_join" | "cross_join" | "distinct"
    left / right: "local" (elided — input already aligned), "shuffle"
    (emitted collective), "broadcast" (small side all_gathered),
    "gather" (cross join's structural replication), "-" (no such side:
    distinct uses `left` for its only input).
    """

    op: str
    key: tuple[str, ...]
    left: str = "-"
    right: str = "-"

    @property
    def emitted(self) -> int:
        return int(self.left == "shuffle") + int(self.right == "shuffle")

    @property
    def elided(self) -> int:
        return int(self.left == "local") + int(self.right == "local")

    @property
    def broadcast(self) -> bool:
        return self.right == "broadcast"


def strategy_counts(strategies) -> dict[str, int]:
    """Aggregate emitted/elided/broadcast counts for stats and explain()."""
    return {
        "emitted": sum(s.emitted for s in strategies),
        "elided": sum(s.elided for s in strategies),
        "broadcast": sum(1 for s in strategies if s.broadcast),
    }


def format_strategy(st: SiteStrategy) -> str:
    """One shuffle site's data-movement decision as the explain() line."""
    if st.op == "cross_join":
        return "right side replicated (all_gather)"
    if st.op == "distinct":
        return (
            "shuffle by all columns (emitted)"
            if st.left == "shuffle"
            else "co-located already (shuffle elided)"
        )
    sides = []
    for name, action in (("left", st.left), ("right", st.right)):
        if action == "local":
            sides.append(f"{name} map-side (shuffle elided)")
        elif action == "shuffle":
            sides.append(f"{name} shuffle emitted")
        elif action == "broadcast":
            sides.append(f"{name} broadcast (all_gather)")
    return ", ".join(sides) + f" on key ({', '.join(st.key)})"


def analyze_plan(
    plan: PhysicalPlan,
    n_shards: int,
    broadcast_rows: int = DEFAULT_BROADCAST_ROWS,
) -> tuple[SiteStrategy, ...]:
    """Propagate Partitioning bottom-up and fix each site's strategy.

    Pure host-side static analysis (capacities and schemas only), so the
    engine can show the chosen/elided shuffles in explain() and count
    them in ExecStats without touching the device. Strategies are in
    shuffle-site order (`shuffle_site_nodes`). Rules:

      Scan      -> hash(subject col) when the subject is a variable
      Filter    -> child's (masks move no rows)
      Project   -> child's if every partition column survives, else unknown
      UnionAll  -> the common child partitioning, if all agree
      Join      -> per side "local" iff its partitioning == hash(key)
                   (trivially true at n_shards == 1); a misaligned small
                   right side broadcasts instead of shuffling both sides;
                   output is hash(key), or the left partitioning under a
                   broadcast (left rows never move)
      Distinct  -> "local" iff the child is hash-partitioned on a subset
                   of its columns (equal rows agree on every column, so
                   they co-locate already); else shuffle by all columns
      Slice     -> child's (global-rank masking moves no rows)
    """
    strategies: list[SiteStrategy] = []
    parts: dict[int, Partitioning] = {}

    def aligned(p: Partitioning, key: tuple[str, ...]) -> bool:
        return n_shards == 1 or (p.kind == "hash" and p.cols == key)

    def restrict(p: Partitioning, schema) -> Partitioning:
        if p.kind == "hash" and not all(c in schema for c in p.cols):
            return UNKNOWN  # a partition column was projected away
        return p

    def part(node: PlanNode) -> Partitioning:
        hit = parts.get(id(node))
        if hit is not None:
            return hit
        p = _part(node)
        parts[id(node)] = p
        return p

    def _part(node: PlanNode) -> Partitioning:
        if isinstance(node, Scan):
            if node.part_col >= 0:
                return hash_part((node.schema[node.part_col],))
            return UNKNOWN
        if isinstance(node, (MRJoin, MatrixJoin, LeftJoin)):
            pl = part(node.left)
            pr = part(node.right)
            key = tuple(node.key_vars)
            op = (
                "left_join" if isinstance(node, LeftJoin)
                else "matrix_join" if isinstance(node, MatrixJoin)
                else "mr_join"
            )
            left = "local" if aligned(pl, key) else "shuffle"
            right = "local" if aligned(pr, key) else "shuffle"
            if (
                left == "shuffle"
                and right == "shuffle"
                and node.right.capacity * n_shards <= broadcast_rows
            ):
                # replicate the small right side and keep every left row
                # in place (sound for LeftJoin too: each left row meets
                # ALL right rows of its key, and exists on exactly one
                # shard, so inner matches and unmatched padding are both
                # globally exact)
                left, right = "local", "broadcast"
                out = restrict(pl, node.schema)
            else:
                out = hash_part(key) if key else UNKNOWN
            strategies.append(SiteStrategy(op, key, left, right))
            return out
        if isinstance(node, CrossJoin):
            pl = part(node.left)
            part(node.right)  # visit: nested sites keep evaluation order
            strategies.append(
                SiteStrategy("cross_join", (), "local", "gather")
            )
            return restrict(pl, node.schema)
        if isinstance(node, Filter):
            return part(node.child)
        if isinstance(node, Project):
            return restrict(part(node.child), node.schema)
        if isinstance(node, UnionAll):
            ps = [part(c) for c in node.children]
            if ps and all(p == ps[0] for p in ps) and ps[0].kind == "hash":
                return restrict(ps[0], node.schema)
            return UNKNOWN
        if isinstance(node, Distinct):
            p = part(node.child)
            schema = tuple(node.schema)
            local = (
                n_shards == 1
                or not schema
                or (p.kind == "hash" and set(p.cols) <= set(schema))
            )
            strategies.append(
                SiteStrategy(
                    "distinct", schema, "local" if local else "shuffle"
                )
            )
            return p if local else hash_part(schema)
        if isinstance(node, Slice):
            return part(node.child)
        raise TypeError(f"unknown plan node {node!r}")

    part(plan.root)
    assert len(strategies) == n_shuffle_sites(plan)
    return tuple(strategies)


# -- shuffle-site enumeration -------------------------------------------------


def shuffle_site_nodes(plan: PhysicalPlan) -> list[PlanNode]:
    """Shuffle sites in evaluation (post-)order: one per join step (MRJoin
    / MatrixJoin / LeftJoin / CrossJoin — the cross join's slot is
    structural) plus one per Distinct. The id-dedup matches the
    evaluator's memoised first-visit order on DAG plans."""
    sites: list[PlanNode] = []
    seen: set[int] = set()

    def walk(node: PlanNode) -> None:
        if id(node) in seen:
            return
        seen.add(id(node))
        for child in child_nodes(node):
            walk(child)
        if isinstance(
            node, (MRJoin, MatrixJoin, LeftJoin, CrossJoin, Distinct)
        ):
            sites.append(node)

    walk(plan.root)
    return sites


def n_shuffle_sites(plan: PhysicalPlan) -> int:
    return len(shuffle_site_nodes(plan))


def n_shuffle_slots(plan: PhysicalPlan, n_stages: int) -> int:
    """Shuffle cap slots: one per site per mesh-axis stage (site-major)."""
    return n_shuffle_sites(plan) * n_stages


def initial_shuffle_caps(
    plan: PhysicalPlan,
    axis_sizes: "tuple[int, ...] | int",
    floor: int = 8,
) -> tuple[int, ...]:
    """Starting shuffle bucket per (site, stage): the uniform-distribution
    estimate — stage k routes rows to axis_sizes[k] destinations, so its
    per-destination load is ~worst-input / axis_sizes[k]. Skewed keys
    overflow the first dispatch, which reports the exact per-stage need —
    one regrow converges, exactly like the join buckets."""
    from repro.core.plan_ir import bucket_capacity

    if isinstance(axis_sizes, int):
        axis_sizes = (axis_sizes,)
    caps: list[int] = []
    for node in shuffle_site_nodes(plan):
        if isinstance(node, Distinct):
            worst = node.capacity
        else:
            worst = max(node.left.capacity, node.right.capacity)
        for size in axis_sizes:
            caps.append(bucket_capacity(max(floor, -(-worst // size))))
    return tuple(caps)


def _collective_free(node: PlanNode, memo: dict[int, bool]) -> bool:
    """True when evaluating `node` runs no collective (so its shuffle can
    be issued ahead of the whole join chain)."""
    hit = memo.get(id(node))
    if hit is not None:
        return hit
    if isinstance(
        node, (MRJoin, MatrixJoin, LeftJoin, CrossJoin, Distinct, Slice)
    ):
        free = False
    else:
        free = all(_collective_free(c, memo) for c in child_nodes(node))
    memo[id(node)] = free
    return free


# -- the lowering -------------------------------------------------------------


def _local_program(
    plan: PhysicalPlan,
    axis_names: tuple[str, ...],
    n_shards: int,
    shuffle_caps: tuple[int, ...],
    strategies: tuple[SiteStrategy, ...],
    use_kernel: bool = False,
) -> Callable[..., ShardedChainResult]:
    """The per-shard program (runs INSIDE shard_map): plan tree -> pure
    function of (scans, consts_i, consts_f, num_vals), accounting with a
    leading singleton shard axis for the out_specs to gather over."""
    n_stages = len(axis_names)
    site_nodes = shuffle_site_nodes(plan)
    site_of = {id(n): i for i, n in enumerate(site_nodes)}
    slot_of = {id(n): k for k, n in enumerate(ex.join_slot_nodes(plan))}
    assert len(shuffle_caps) == len(site_nodes) * n_stages, (
        shuffle_caps, len(site_nodes), n_stages,
    )

    def site_caps(i: int) -> tuple[int, ...]:
        return tuple(shuffle_caps[i * n_stages:(i + 1) * n_stages])

    def flat_rank() -> jax.Array:
        rank = jnp.int32(0)
        for a in axis_names:
            rank = rank * compat.axis_size(a) + jax.lax.axis_index(a)
        return rank

    def gather_rows(x: jax.Array) -> jax.Array:
        """all_gather rows over the mesh, ordered by flat shard rank."""
        for a in reversed(axis_names):
            x = jax.lax.all_gather(x, a, axis=0, tiled=True)
        return x

    def local_run(
        scans: tuple[Relation, ...],
        consts_i: jax.Array,
        consts_f: jax.Array,
        num_vals: jax.Array,
    ) -> ShardedChainResult:
        totals: list[jax.Array] = []
        flags: list[jax.Array] = []
        sh_needs: list = [None] * len(site_nodes)
        sh_flags: list = [None] * len(site_nodes)
        memo: dict[int, Relation] = {}
        slots = dj.ShuffleSlots()

        def zero_acct():
            return (
                jnp.zeros((n_stages,), jnp.int32),
                jnp.zeros((n_stages,), bool),
            )

        def shuffled(node: PlanNode, side: str, rel: Relation):
            """Shuffle one join input by the node's key — consuming the
            prestaged double-buffer slot when the overlap pass issued it."""
            slot = (id(node), side)
            caps = site_caps(site_of[id(node)])
            if slots.ready(slot):
                cols, valid, ov, need = slots.take(slot)
            else:
                idx = [rel.schema.index(v) for v in node.key_vars]
                cols, valid, ov, need = dj.shuffle_by_key(
                    rel.cols, rel.valid, idx, axis_names, caps
                )
            return Relation(rel.schema, cols, valid), ov, need

        def replicate(rel: Relation) -> Relation:
            return Relation(
                rel.schema, gather_rows(rel.cols), gather_rows(rel.valid)
            )

        def eval_node(node: PlanNode) -> Relation:
            hit = memo.get(id(node))
            if hit is not None:
                return hit
            # children first, so each node's scope holds its own ops only
            for kid in child_nodes(node):
                eval_node(kid)
            with jax.named_scope(ex.op_scope(node, slot_of)):
                rel = _eval(node)
            memo[id(node)] = rel
            return rel

        def _eval(node: PlanNode) -> Relation:
            if isinstance(node, Scan):
                return scans[node.index]
            if isinstance(node, (MRJoin, MatrixJoin, LeftJoin)):
                si = site_of[id(node)]
                st = strategies[si]
                left = eval_node(node.left)
                right = eval_node(node.right)
                need, ov_sh = zero_acct()
                with jax.named_scope("shuffle"):
                    if st.left == "shuffle":
                        left, ov, nd = shuffled(node, "left", left)
                        need, ov_sh = jnp.maximum(need, nd), ov_sh | ov
                    if st.right == "shuffle":
                        right, ov, nd = shuffled(node, "right", right)
                        need, ov_sh = jnp.maximum(need, nd), ov_sh | ov
                    elif st.right == "broadcast":
                        right = replicate(right)
                if isinstance(node, LeftJoin):
                    ljoin = (
                        mxj.matrix_left_join if node.backend == "matrix"
                        else mj.left_join
                    )
                    out, total, ovf = ljoin(
                        left, right, capacity=node.join_cap,
                        use_kernel=use_kernel,
                    )
                else:
                    join = (
                        mxj.matrix_join if isinstance(node, MatrixJoin)
                        else mj.mr_join
                    )
                    out, total, ovf = join(
                        left, right, capacity=node.capacity,
                        use_kernel=use_kernel,
                    )
                totals.append(total)
                flags.append(ovf)
                sh_needs[si], sh_flags[si] = need, ov_sh
                return out
            if isinstance(node, CrossJoin):
                si = site_of[id(node)]
                left = eval_node(node.left)
                right = eval_node(node.right)
                with jax.named_scope("shuffle"):
                    r_all = replicate(right)
                # every (local-left, global-right) position is enumerated:
                # exact, like the single-device cross join
                out, total, ovf = mj.cross_join(
                    left, r_all, capacity=left.capacity * r_all.capacity
                )
                totals.append(total)
                flags.append(ovf)
                sh_needs[si], sh_flags[si] = zero_acct()
                return mj.compact(out)
            if isinstance(node, Filter):
                child = eval_node(node.child)
                keep = mj.filter_mask(
                    child, node.conds, consts_i, consts_f, num_vals
                )
                return Relation(child.schema, child.cols, keep)
            if isinstance(node, UnionAll):
                kids = [eval_node(c) for c in node.children]
                return mj.union_all(kids, node.schema)
            if isinstance(node, Project):
                return eval_node(node.child).project(list(node.schema))
            if isinstance(node, Distinct):
                si = site_of[id(node)]
                st = strategies[si]
                child = eval_node(node.child)
                if st.left == "shuffle":
                    # co-locate equal rows at a calibrated per-shard
                    # bucket; elided when the child is already hash-
                    # partitioned on a subset of its columns
                    idx = list(range(child.n_cols))
                    with jax.named_scope("shuffle"):
                        cols, valid, ov, need = dj.shuffle_by_key(
                            child.cols, child.valid, idx, axis_names,
                            site_caps(si),
                        )
                    child = Relation(child.schema, cols, valid)
                    sh_needs[si], sh_flags[si] = need, ov
                else:
                    sh_needs[si], sh_flags[si] = zero_acct()
                return mj.distinct(child)
            if isinstance(node, Slice):
                child = eval_node(node.child)
                count = child.count().astype(jnp.int32)
                counts = gather_rows(count[None])  # (n_shards,)
                my = flat_rank()
                prev = jnp.sum(
                    jnp.where(
                        jnp.arange(n_shards) < my, counts, 0
                    )
                )
                offset = consts_i[node.offset_index]
                limit = consts_i[node.limit_index]
                rank = prev + jnp.cumsum(child.valid.astype(jnp.int32))
                keep = (
                    child.valid
                    & (rank > offset)
                    & (rank <= offset + limit)
                )
                return Relation(child.schema, child.cols, keep)
            raise TypeError(f"unknown plan node {node!r}")

        # overlap prestage: issue every emitted shuffle whose input is a
        # collective-free subtree BEFORE the join chain runs, so the
        # collective for join step k+1 is already in flight while step
        # k's local join computes (ShuffleSlots double buffering)
        free_memo: dict[int, bool] = {}
        for node in site_nodes:
            if not isinstance(node, (MRJoin, MatrixJoin, LeftJoin)):
                continue
            st = strategies[site_of[id(node)]]
            for side, child, action in (
                ("left", node.left, st.left),
                ("right", node.right, st.right),
            ):
                if action == "shuffle" and _collective_free(
                    child, free_memo
                ):
                    rel = eval_node(child)
                    idx = [rel.schema.index(v) for v in node.key_vars]
                    with jax.named_scope(ex.op_scope(node, slot_of)), \
                            jax.named_scope("shuffle"):
                        slots.issue(
                            (id(node), side), rel.cols, rel.valid, idx,
                            axis_names, site_caps(site_of[id(node)]),
                        )

        rel = eval_node(plan.root)
        n_joins = len(totals)
        totals_arr = (
            jnp.stack(totals)[None] if totals
            else jnp.zeros((1, 0), jnp.int32)
        )
        flags_arr = (
            jnp.stack(flags)[None] if flags
            else jnp.zeros((1, 0), bool)
        )
        assert all(x is not None for x in sh_needs), sh_needs
        needs_arr = (
            jnp.concatenate(sh_needs)[None] if sh_needs
            else jnp.zeros((1, 0), jnp.int32)
        )
        sh_flags_arr = (
            jnp.concatenate(sh_flags)[None] if sh_flags
            else jnp.zeros((1, 0), bool)
        )
        assert n_joins == len(plan.join_caps), (n_joins, plan.join_caps)
        return ShardedChainResult(
            rel, totals_arr, flags_arr, needs_arr, sh_flags_arr
        )

    return local_run


def _mesh_shards(mesh: jax.sharding.Mesh, axis_names) -> int:
    n = 1
    for a in axis_names:
        n *= mesh.shape[a]
    return n


def lower_sharded(
    plan: PhysicalPlan,
    mesh: jax.sharding.Mesh,
    axis_names: tuple[str, ...],
    shuffle_caps: tuple[int, ...],
    use_kernel: bool = False,
    broadcast_rows: int = DEFAULT_BROADCAST_ROWS,
) -> Callable[..., ShardedChainResult]:
    """Plan tree -> shard_mapped function of (scans, consts_i, consts_f,
    num_vals) with the same call signature as the single-device program.

    Join/shuffle accounting is collected in evaluation order — the same
    order `build_plan` consumes join_caps in. `shuffle_caps` carries
    n_shuffle_slots(plan, len(axis_names)) entries: per shuffle site
    (join steps in join_caps order — cross joins keep a structural slot —
    plus one per Distinct), one bucket per mesh-axis stage."""
    n_shards = _mesh_shards(mesh, axis_names)
    strategies = analyze_plan(plan, n_shards, broadcast_rows)
    local_run = _local_program(
        plan, axis_names, n_shards, shuffle_caps, strategies,
        use_kernel=use_kernel,
    )
    row = P(axis_names)
    scan_specs = tuple(
        Relation(node_schema, row, row)
        for node_schema in _scan_schemas(plan)
    )
    rep = P()
    out_specs = ShardedChainResult(
        Relation(plan.root.schema, row, row), row, row, row, row
    )
    return compat.shard_map(
        local_run,
        mesh=mesh,
        in_specs=(scan_specs, rep, rep, rep),
        out_specs=out_specs,
        check_vma=False,
    )


def _scan_schemas(plan: PhysicalPlan) -> list[tuple[str, ...]]:
    """Scan schemas by scan index (for the in_spec pytree)."""
    out: dict[int, tuple[str, ...]] = {}
    seen: set[int] = set()

    def walk(node: PlanNode) -> None:
        if id(node) in seen:
            return
        seen.add(id(node))
        if isinstance(node, Scan):
            out[node.index] = node.schema
        for child in child_nodes(node):
            walk(child)

    walk(plan.root)
    return [out[i] for i in range(plan.n_scans)]


@dataclasses.dataclass
class CompiledShardedPlan:
    """An XLA mesh executable specialised on one (shape, per-shard join
    caps, per-shard per-stage shuffle caps) point. Call-compatible with
    executor.CompiledPlan so the engine's cache entries can hold either.
    `strategies` records each site's chosen data movement (emitted /
    elided / broadcast) for stats and explain()."""

    plan: PhysicalPlan
    shuffle_caps: tuple[int, ...]
    n_shards: int
    executable: Any  # jax.stages.Compiled
    strategies: tuple[SiteStrategy, ...] = ()

    def __call__(
        self,
        scans: tuple[Relation, ...],
        consts_i: jax.Array,
        consts_f: jax.Array,
        num_vals: jax.Array,
    ) -> ShardedChainResult:
        return self.executable(scans, consts_i, consts_f, num_vals)


def compile_sharded_plan(
    plan: PhysicalPlan,
    mesh: jax.sharding.Mesh,
    axis_names: tuple[str, ...],
    shuffle_caps: tuple[int, ...],
    scans: tuple[Relation, ...],
    consts_i: jax.Array,
    consts_f: jax.Array,
    num_vals: jax.Array,
    use_kernel: bool = False,
    broadcast_rows: int = DEFAULT_BROADCAST_ROWS,
) -> CompiledShardedPlan:
    """AOT-compile the sharded program against the inputs' static shapes
    (compilation is the only XLA entry point, so the engine's n_compiles
    accounting stays exact — warm queries must report zero)."""
    n_shards = _mesh_shards(mesh, axis_names)
    fn = jax.jit(
        lower_sharded(
            plan, mesh, axis_names, shuffle_caps, use_kernel=use_kernel,
            broadcast_rows=broadcast_rows,
        )
    )
    executable = fn.lower(scans, consts_i, consts_f, num_vals).compile()
    return CompiledShardedPlan(
        plan, shuffle_caps, n_shards, executable,
        analyze_plan(plan, n_shards, broadcast_rows),
    )


# -- batched (lanes x shards) execution ---------------------------------------


def lower_sharded_batched(
    plan: PhysicalPlan,
    mesh: jax.sharding.Mesh,
    axis_names: tuple[str, ...],
    shuffle_caps: tuple[int, ...],
    scan_axes: tuple,
    use_kernel: bool = False,
    broadcast_rows: int = DEFAULT_BROADCAST_ROWS,
) -> Callable[..., ShardedChainResult]:
    """Stacked variant of `lower_sharded`: ONE mesh dispatch executes a
    whole lane batch of warm same-shape queries (lanes x shards), the
    distributed mirror of executor.lower_batched.

    Inside shard_map the per-shard program is vmapped over the lane axis;
    the shuffle/gather collectives batch under vmap (each lane's
    all_to_all rides the same launch). `scan_axes` is the per-scan vmap
    axis: 0 for a (width, n_shards * cap, n_cols) stacked buffer, None
    for a broadcast scan every lane shares. A `(width,)` bool
    `lane_active` mask zeroes padding lanes' scan validity and overflow
    flags, so padding can never emit rows or trigger a regrow."""
    n_shards = _mesh_shards(mesh, axis_names)
    strategies = analyze_plan(plan, n_shards, broadcast_rows)
    local_run = _local_program(
        plan, axis_names, n_shards, shuffle_caps, strategies,
        use_kernel=use_kernel,
    )

    def lane(
        scans: tuple[Relation, ...],
        consts_i: jax.Array,
        consts_f: jax.Array,
        num_vals: jax.Array,
        active: jax.Array,
    ) -> ShardedChainResult:
        masked = []
        for j, s in enumerate(scans):
            with jax.named_scope(f"scan{j}"):
                masked.append(Relation(s.schema, s.cols, s.valid & active))
        res = local_run(tuple(masked), consts_i, consts_f, num_vals)
        return ShardedChainResult(
            res.relation,
            res.totals,
            res.overflows & active,
            res.shuffle_needs,
            res.shuffle_flags & active,
        )

    local_batched = jax.vmap(
        lane, in_axes=(tuple(scan_axes), 0, 0, None, 0)
    )
    row = P(axis_names)
    lane_row = P(None, axis_names)
    scan_specs = tuple(
        Relation(
            schema,
            lane_row if ax == 0 else row,
            lane_row if ax == 0 else row,
        )
        for schema, ax in zip(_scan_schemas(plan), scan_axes)
    )
    rep = P()
    out_specs = ShardedChainResult(
        Relation(plan.root.schema, lane_row, lane_row),
        lane_row, lane_row, lane_row, lane_row,
    )
    return compat.shard_map(
        local_batched,
        mesh=mesh,
        in_specs=(scan_specs, rep, rep, rep, rep),
        out_specs=out_specs,
        check_vma=False,
    )


@dataclasses.dataclass
class CompiledShardedBatch:
    """A width-W lanes-x-shards mesh executable for one (shape, join caps,
    shuffle caps) point — any group of <= W warm same-shape queries whose
    scans stack the same way dispatches through it."""

    plan: PhysicalPlan
    width: int
    shuffle_caps: tuple[int, ...]
    n_shards: int
    executable: Any  # jax.stages.Compiled
    scan_axes: tuple = ()
    strategies: tuple[SiteStrategy, ...] = ()

    def __call__(
        self,
        scans: tuple[Relation, ...],
        consts_i: jax.Array,
        consts_f: jax.Array,
        num_vals: jax.Array,
        lane_active: jax.Array,
    ) -> ShardedChainResult:
        return self.executable(
            scans, consts_i, consts_f, num_vals, lane_active
        )


def compile_sharded_plan_batched(
    plan: PhysicalPlan,
    mesh: jax.sharding.Mesh,
    axis_names: tuple[str, ...],
    shuffle_caps: tuple[int, ...],
    scans: tuple[Relation, ...],
    consts_i: jax.Array,
    consts_f: jax.Array,
    num_vals: jax.Array,
    lane_active: jax.Array,
    scan_axes: tuple,
    use_kernel: bool = False,
    broadcast_rows: int = DEFAULT_BROADCAST_ROWS,
) -> CompiledShardedBatch:
    """AOT-compile the stacked sharded program at the inputs' batch width
    (scans at a None axis in `scan_axes` arrive UNstacked)."""
    n_shards = _mesh_shards(mesh, axis_names)
    fn = jax.jit(
        lower_sharded_batched(
            plan, mesh, axis_names, shuffle_caps, tuple(scan_axes),
            use_kernel=use_kernel, broadcast_rows=broadcast_rows,
        )
    )
    executable = fn.lower(
        scans, consts_i, consts_f, num_vals, lane_active
    ).compile()
    return CompiledShardedBatch(
        plan,
        int(lane_active.shape[0]),
        shuffle_caps,
        n_shards,
        executable,
        tuple(scan_axes),
        analyze_plan(plan, n_shards, broadcast_rows),
    )
