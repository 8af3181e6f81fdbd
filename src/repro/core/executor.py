"""Compiled executor: lower a PhysicalPlan to ONE jitted device program.

The eager engine dispatches per join (count pass, host sync, expand pass).
This module instead lowers the whole plan tree — every MapReduce join, the
cross joins, OPTIONAL left joins, FILTER masks, projection, DISTINCT and
LIMIT/OFFSET — into a single function of the scan relations plus the
runtime constants, then AOT-compiles it with `jax.jit(...).lower(...)
.compile()`.

A warm query is therefore exactly one device dispatch. The per-join exact
totals and overflow flags ride back in that same dispatch, so the host's
only synchronisation is reading the flags afterwards; when a bucket
overflowed, the engine grows it (plan_ir.grow_join_caps) and recompiles —
the Mars double-on-overflow discipline demoted to a rare fallback.

Runtime constants keep the cache hot across query variants: FILTER
comparison constants arrive as `consts_i` (term ids) / `consts_f` (numeric
values), LIMIT/OFFSET ride at the tail of `consts_i`, and `num_vals` is
the store's per-term numeric table — all plain inputs, none baked into the
executable.

AOT compilation (rather than relying on jit's implicit cache) keeps the
compile count observable: `compile_plan` / `compile_plan_batched` are the
only places XLA compilation happens, so ExecStats.n_compiles is exact and
tests can assert a warm cache compiles nothing.

`lower_batched` / `compile_plan_batched` stack W same-shape queries into
ONE device dispatch: the plan program is vmapped over the scan relations
and runtime constants (leading batch axis), with a lane-validity mask so
padded lanes contribute no rows and no overflow flags.
"""
from __future__ import annotations

import collections
import dataclasses
import re
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

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


class ChainResult(NamedTuple):
    """Everything one dispatch returns (all device-resident)."""

    relation: Relation
    totals: jax.Array  # (n_joins,) exact per-join cardinality
    overflows: jax.Array  # (n_joins,) bool: join i truncated its output


def lower(
    plan: PhysicalPlan, use_kernel: bool = False
) -> Callable[..., ChainResult]:
    """Plan tree -> a pure function of (scans, consts_i, consts_f, num_vals).

    Join totals/overflows are collected in evaluation (post-)order: the
    required chain first, then each OPTIONAL group's inner joins followed
    by its left join — the order the engine calibrates join_caps in.
    """

    slot_of = {id(n): k for k, n in enumerate(join_slot_nodes(plan))}

    def run(
        scans: tuple[Relation, ...],
        consts_i: jax.Array,
        consts_f: jax.Array,
        num_vals: jax.Array,
    ) -> ChainResult:
        totals: list[jax.Array] = []
        flags: list[jax.Array] = []
        # The plan may be a DAG: UNION branches share the required-chain
        # subtree. Memoising by node identity evaluates the shared subtree
        # once, so its join totals/overflows are reported exactly once (in
        # first-visit order — the order the engine calibrates join_caps in).
        memo: dict[int, Relation] = {}

        def eval_node(node: PlanNode) -> Relation:
            hit = memo.get(id(node))
            if hit is not None:
                return hit
            # children first, so each node's scope holds its own ops only
            for kid in child_nodes(node):
                eval_node(kid)
            with jax.named_scope(op_scope(node, slot_of)):
                rel = _eval(node)
            memo[id(node)] = rel
            return rel

        def _eval(node: PlanNode) -> Relation:
            if isinstance(node, Scan):
                return scans[node.index]
            if isinstance(node, (MRJoin, MatrixJoin)):
                left = eval_node(node.left)
                right = eval_node(node.right)
                join = (
                    mxj.matrix_join if isinstance(node, MatrixJoin)
                    else mj.mr_join
                )
                out, total, ovf = join(
                    left, right, capacity=node.capacity, use_kernel=use_kernel
                )
                totals.append(total)
                flags.append(ovf)
                return out
            if isinstance(node, CrossJoin):
                left = eval_node(node.left)
                right = eval_node(node.right)
                out, total, ovf = mj.cross_join(
                    left, right, capacity=node.capacity
                )
                totals.append(total)
                flags.append(ovf)
                return mj.compact(out)
            if isinstance(node, LeftJoin):
                left = eval_node(node.left)
                right = eval_node(node.right)
                ljoin = (
                    mxj.matrix_left_join if node.backend == "matrix"
                    else mj.left_join
                )
                out, total, ovf = ljoin(
                    left, right, capacity=node.join_cap, use_kernel=use_kernel
                )
                totals.append(total)
                flags.append(ovf)
                return out
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
                return mj.distinct(eval_node(node.child))
            if isinstance(node, Slice):
                child = eval_node(node.child)
                return mj.slice_valid(
                    child,
                    consts_i[node.offset_index],
                    consts_i[node.limit_index],
                )
            raise TypeError(f"unknown plan node {node!r}")

        rel = eval_node(plan.root)
        totals_arr = (
            jnp.stack(totals) if totals else jnp.zeros((0,), jnp.int32)
        )
        flags_arr = jnp.stack(flags) if flags else jnp.zeros((0,), bool)
        return ChainResult(rel, totals_arr, flags_arr)

    return run


def join_slot_nodes(plan: PhysicalPlan) -> list[PlanNode]:
    """The join nodes of a plan in slot order — the order `lower` appends
    their totals/overflow flags (post-order, shared DAG subtrees visited
    once, in first-visit order). EXPLAIN ANALYZE uses this to label each
    actuals slot with its physical operator; it MUST mirror `lower`'s
    traversal exactly or actuals would land on the wrong node."""
    slots: list[PlanNode] = []
    seen: set[int] = set()

    def walk(node: PlanNode) -> None:
        if id(node) in seen:
            return
        seen.add(id(node))
        for attr in ("left", "right", "child"):
            kid = getattr(node, attr, None)
            if kid is not None:
                walk(kid)
        for kid in getattr(node, "children", ()):
            walk(kid)
        if isinstance(node, (MRJoin, MatrixJoin, CrossJoin, LeftJoin)):
            slots.append(node)

    walk(plan.root)
    return slots


# -- plan operators named on the device ---------------------------------------
# `lower` opens a `jax.named_scope` per plan node (joins by slot, as
# `join_slot_nodes` numbers them) and the join algorithms one per phase, so
# every HLO instruction's metadata carries `op_name="jit(run)/join2/count/
# ..."`. A profiler trace names device ops by HLO instruction only;
# `hlo_op_scopes` recovers each instruction's scope from the executable.

_NODE_SCOPES = {
    Filter: "filter",
    UnionAll: "union",
    Project: "project",
    Distinct: "distinct",
    Slice: "slice",
}
_NODE_SCOPE = re.compile(
    r"join\d+|scan\d+|filter|union|project|distinct|slice"
)
_JOIN_PHASE = re.compile(r"map|sort|count|expand|layout|shuffle")
_WRAPPED = re.compile(r"[\w-]+\((.*)\)")
_INSTR = re.compile(r"\s*(?:ROOT )?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")


def op_scope(node: PlanNode, slot_of: dict[int, int]) -> str:
    """The device scope of one plan node: `join<slot>` for every join
    (slot numbering of `join_slot_nodes`), `scan<j>` for scan input j."""
    if id(node) in slot_of:
        return f"join{slot_of[id(node)]}"
    if isinstance(node, Scan):
        return f"scan{node.index}"
    return _NODE_SCOPES[type(node)]


def scope_of(op_name: str) -> str:
    """The plan-operator scope inside an HLO `op_name`:
    'jit(run)/join2/expand/jit(searchsorted)/while' -> 'join2/expand';
    transform wrappers ('vmap(join0)', 'vmap()', 'shard_map') are looked
    through, and the last part, the primitive's own name, is never a
    scope. '' when the op sits under no plan operator."""
    parts: list[str] = []
    for part in op_name.split("/")[1:-1]:
        if part == "shard_map":
            continue
        m = _WRAPPED.fullmatch(part)
        if m is not None:
            part = m.group(1)
            if not part:
                continue
        if not parts:
            ok = _NODE_SCOPE.fullmatch(part)
        else:
            ok = len(parts) == 1 and parts[0].startswith("join") and (
                _JOIN_PHASE.fullmatch(part))
        if not ok:
            break
        parts.append(part)
    return "/".join(parts)


def hlo_op_scopes(hlo_text: str) -> dict[str, str]:
    """HLO instruction name -> plan-operator scope, from a compiled
    module's text (`Compiled.as_text()`). A fusion without metadata of its
    own takes the most common scope of the computation it calls."""
    own: dict[str, str] = {}
    calls: dict[str, str] = {}
    by_comp: dict[str, collections.Counter] = {}
    comp = ""
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            comp = line.split()[0].lstrip("%")
            if comp == "ENTRY":
                comp = line.split()[1].lstrip("%")
            continue
        m = _INSTR.match(line)
        if m is None:
            continue
        name = m.group(1)
        on = _OP_NAME.search(line)
        sc = scope_of(on.group(1)) if on else ""
        own[name] = sc
        if sc:
            by_comp.setdefault(comp, collections.Counter())[sc] += 1
        c = _CALLS.search(line)
        if c is not None:
            calls[name] = c.group(1)
    for name, callee in calls.items():
        if not own[name] and callee in by_comp:
            own[name] = by_comp[callee].most_common(1)[0][0]
    return own


def module_key(compiled: Any) -> str:
    """One AOT executable's (`jax.stages.Compiled`) name in `op_scopes()`
    and on its `mapsq.launch` annotation: the HLO module's name and the
    executable's fingerprint, `jit_run(<fingerprint>)`."""
    rt = compiled.runtime_executable()
    fp = rt.fingerprint
    if isinstance(fp, bytes):
        try:
            fp = fp.decode()
        except UnicodeDecodeError:
            fp = fp.hex()
    return f"{rt.hlo_modules()[0].name}({fp})"


@dataclasses.dataclass
class CompiledPlan:
    """An XLA executable specialised on one (shape, join-caps) point."""

    plan: PhysicalPlan
    executable: Any  # jax.stages.Compiled
    n_joins: int

    def __call__(
        self,
        scans: tuple[Relation, ...],
        consts_i: jax.Array,
        consts_f: jax.Array,
        num_vals: jax.Array,
    ) -> ChainResult:
        return self.executable(scans, consts_i, consts_f, num_vals)


def compile_plan(
    plan: PhysicalPlan,
    scans: tuple[Relation, ...],
    consts_i: jax.Array,
    consts_f: jax.Array,
    num_vals: jax.Array,
    use_kernel: bool = False,
) -> CompiledPlan:
    """AOT-compile the plan against the inputs' (static) shapes.

    The executable accepts any input tuple with the same schemas/capacities
    — i.e. every future query that hashes to the same PlanShape.
    """
    fn = jax.jit(lower(plan, use_kernel=use_kernel))
    executable = fn.lower(scans, consts_i, consts_f, num_vals).compile()
    return CompiledPlan(plan, executable, len(plan.join_caps))


# -- batched (stacked same-shape) execution -----------------------------------


def lower_batched(
    plan: PhysicalPlan,
    use_kernel: bool = False,
    scan_axes: "tuple[int | None, ...] | None" = None,
) -> Callable[..., ChainResult]:
    """Stacked variant of `lower`: one dispatch executes a whole lane batch
    of same-shape queries.

    Every per-query runtime input — the scan relations, `consts_i`,
    `consts_f` — gains a leading batch axis; the store-wide `num_vals`
    table stays shared. A `(width,)` bool `lane_active` mask marks which
    lanes carry real queries: an inactive (padding) lane has its scan
    validity zeroed before anything else runs, so no operator downstream —
    join expansion, OPTIONAL unmatched-left padding, UNION concatenation —
    can emit a valid row for it, and its overflow flags are suppressed so
    padding can never trigger a bucket regrow.

    `scan_axes` is the per-scan vmap axis: 0 for a stacked (width, cap,
    n_cols) buffer, None for a BROADCAST scan every lane shares — the
    same-query-different-FILTER batch ships each such scan's device buffer
    once instead of W stacked copies, cutting staging memory by the batch
    width at those positions. Default: all stacked.

    Lanes need NOT stage at their natural scan capacities: cross-shape
    padded stacking (engine._coalesce_groups) runs near-miss PlanShapes —
    same plan DAG, smaller pow-2 scan caps — through one executable by
    padding each lane's scans up to the group's max caps. Padding rows
    arrive valid=False, and every operator here is masked on validity, so
    a padded lane emits exactly the rows its natural shape would have.
    """
    base = lower(plan, use_kernel=use_kernel)
    axes = scan_axes if scan_axes is not None else (0,) * plan.n_scans

    def run_lane(
        scans: tuple[Relation, ...],
        consts_i: jax.Array,
        consts_f: jax.Array,
        num_vals: jax.Array,
        active: jax.Array,
    ) -> ChainResult:
        masked = []
        for j, s in enumerate(scans):
            with jax.named_scope(f"scan{j}"):
                masked.append(Relation(s.schema, s.cols, s.valid & active))
        masked = tuple(masked)
        rel, totals, flags = base(masked, consts_i, consts_f, num_vals)
        return ChainResult(rel, totals, flags & active)

    return jax.vmap(run_lane, in_axes=(tuple(axes), 0, 0, None, 0))


@dataclasses.dataclass
class CompiledBatch:
    """A width-W stacked executable for one (shape, join-caps) point.

    Same specialisation as CompiledPlan plus the batch width and the
    per-scan stacked/broadcast layout: any group of <= W same-shape
    queries whose scans stack the same way dispatches through it
    (trailing lanes padded, masked inactive)."""

    plan: PhysicalPlan
    width: int
    executable: Any  # jax.stages.Compiled
    scan_axes: "tuple[int | None, ...]" = ()

    def __call__(
        self,
        scans: tuple[Relation, ...],
        consts_i: jax.Array,
        consts_f: jax.Array,
        num_vals: jax.Array,
        lane_active: jax.Array,
    ) -> ChainResult:
        return self.executable(scans, consts_i, consts_f, num_vals, lane_active)


def compile_plan_batched(
    plan: PhysicalPlan,
    scans: tuple[Relation, ...],
    consts_i: jax.Array,
    consts_f: jax.Array,
    num_vals: jax.Array,
    lane_active: jax.Array,
    use_kernel: bool = False,
    scan_axes: "tuple[int | None, ...] | None" = None,
) -> CompiledBatch:
    """AOT-compile the stacked variant at the inputs' batch width (scans
    at a None axis in `scan_axes` must arrive UNstacked, (cap, n_cols))."""
    if scan_axes is None:
        scan_axes = (0,) * plan.n_scans
    fn = jax.jit(
        lower_batched(plan, use_kernel=use_kernel, scan_axes=scan_axes)
    )
    executable = fn.lower(
        scans, consts_i, consts_f, num_vals, lane_active
    ).compile()
    return CompiledBatch(
        plan, int(lane_active.shape[0]), executable, tuple(scan_axes)
    )


def execute_plan(
    plan: PhysicalPlan,
    scans: tuple[Relation, ...],
    consts_i: jax.Array,
    consts_f: jax.Array,
    num_vals: jax.Array,
    use_kernel: bool = False,
) -> ChainResult:
    """Uncompiled (op-by-op) interpretation — for tests and debugging."""
    return lower(plan, use_kernel=use_kernel)(
        scans, consts_i, consts_f, num_vals
    )
