"""Reduce a profiler trace to where the device's idle time goes, by
serving phase, and where its busy time goes, by plan operator.

With a tracer, the program opens `mapsq.<phase>` annotations on the
thread doing each phase's work: the batcher thread `wait`, `collect` and
`batch`, and inside a batch `prepare`, `stage`, `launch`, `sync`; decode
workers `transfer` and `decode`. Every stretch of the window in which no
operation runs on the device is split by the innermost phase the
batcher thread was in:

  dispatch  prepare, stage or launch
  sync      sync (waiting on a dispatch's overflow flags)
  batch     in a batch, between those phases: bookkeeping between
            dispatches, the hand-off of results (and any wait for the
            interpreter lock there)
  decode    wait or collect while a decode worker is in transfer or decode
            (or the batcher itself decodes, with no decode pool)
  wait      wait or collect, no decode running
  other     a mapsq.* phase not named above
  none      no mapsq.* phase on the batcher thread

The parts sum to the idle time. Device ops are attributed to plan
operators through the engine's `op_scopes()`: the trace names an op by its
HLO instruction (`%fusion.165 = ...`) on the "XLA Ops" line, inside an
"XLA Modules" event (`jit_run(<number>)`). That module is the executable
whose `mapsq.launch` annotation, which carries its `op_scopes()` key as
the stat `module`, came last before the module started on the device
(the batcher waits on each dispatch before it launches the next), and
has the same module name. Busy time, window and device choice are those
of `xplane.reduce_planes`.

On several chips, the exposed collective time is read on every chip:
the seconds in which a collective op (all-to-all, all-gather,
all-reduce, collective-permute, reduce-scatter, or the start or done of
one) runs and no other op does, taken as the mean over the chips.
"""
from __future__ import annotations

import dataclasses
import re

import numpy as np

from xplane import OPS_LINES, WINDOW, _union

PREFIX = "mapsq."
MODULES_LINE = "XLA Modules"
LAUNCHES = "mapsq.launches"  # host pseudo-line: (module key, start, dur)
LINK_SLACK_NS = 1e6
BATCHER = ("wait", "collect")  # only the batcher thread opens these
DECODE = ("transfer", "decode")
DISPATCH = ("prepare", "stage", "launch")
PARTS = ("dispatch", "sync", "batch", "decode", "wait", "other", "none")
NAME_CHARS = 120  # an op's name as reported: its HLO instruction's head
_INSTR = re.compile(r"%?([\w.\-]+)")
_COLLECTIVE = re.compile(
    r"all-to-all|all-gather|all-reduce|collective-permute|reduce-scatter")


@dataclasses.dataclass
class Phases:
    window_s: float
    busy_s: float
    idle_s: dict  # part -> idle seconds of the device (PARTS)
    batcher_s: dict  # batcher phase -> seconds inside the window
    decode_s: dict  # decode-worker phase -> seconds, summed over workers
    gaps: list  # [[seconds, batcher phase covering most, decode share]]
    top_ops: list  # [[op name, seconds, scope or None]], heaviest first
    scope_s: dict | None  # plan-operator scope -> device seconds
    scoped_s: float | None  # device seconds under any plan operator
    join_s: float | None  # device seconds under a join<k> scope
    modules: int  # XLA module runs in the window
    modules_known: int  # ... tied to an executable op_scopes() names
    modules_unscoped: int  # ... known, but compiled without scopes
    module_keys: list  # [[trace module name, op_scopes() key]], distinct
    collective_exposed_s: float | None  # mean over chips; None: none ran

    def idle_share(self, part: str) -> float:
        return self.idle_s[part] / self.window_s


def load(path: str):
    """`xplane.load`'s planes, each host plane with one more line,
    LAUNCHES: every `mapsq.launch` event, named by its `module` stat."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        lines, launches = [], []
        for line in plane.lines:
            evs = []
            for ev in line.events:
                e = (ev.name, float(ev.start_ns), float(ev.duration_ns))
                evs.append(e)
                if ev.name == PREFIX + "launch":
                    mod = dict(ev.stats).get("module")
                    if mod is not None:
                        launches.append((str(mod),) + e[1:])
            lines.append((line.name, evs))
        if launches:
            lines.append((LAUNCHES, launches))
        out.append((plane.name, lines))
    return out


def _clip(ivs, w0, w1) -> np.ndarray:
    a = np.asarray(ivs, np.float64).reshape(-1, 2)
    a = np.stack([np.maximum(a[:, 0], w0), np.minimum(a[:, 1], w1)], 1)
    return _union(a[a[:, 1] > a[:, 0]])


def _inside(iv: np.ndarray, t: np.ndarray) -> np.ndarray:
    """Which of the points `t` fall in the disjoint sorted intervals."""
    if len(iv) == 0:
        return np.zeros(len(t), bool)
    i = np.searchsorted(iv[:, 0], t, side="right") - 1
    ok = i >= 0
    out = np.zeros(len(t), bool)
    out[ok] = t[ok] < iv[i[ok], 1]
    return out


def _total(iv: np.ndarray) -> float:
    return float((iv[:, 1] - iv[:, 0]).sum()) if len(iv) else 0.0


def reduce_planes(planes, scopes: dict | None = None,
                  top: int = 10) -> Phases | None:
    """`planes` as `load` gives them (without the launches, module runs
    are tied to no executable); `scopes` as the engine's `op_scopes()`
    gives them (module key -> {instruction: scope}), or None.
    Returns None where no device operation ran inside the window or no
    batcher thread left a `mapsq.wait`/`mapsq.collect` annotation. The
    plan-operator numbers are None without scopes, and where a module that
    ran was compiled without them (a persistent-cache hit on a build that
    named no scopes): they would count its time as no operator's."""
    window, device, modules, launches = None, None, [], []
    chips = []  # the ops of every device plane that has any
    threads = []  # per host line: {phase: [(start, end)]}
    for pname, lines in planes:
        if pname.startswith("/device:"):
            ops = [ev for lname, evs in lines if lname in OPS_LINES
                   for ev in evs]
            if ops:
                chips.append(ops)
            if ops and device is None:
                device = ops
                modules = [ev for lname, evs in lines
                           if lname == MODULES_LINE for ev in evs]
            continue
        for lname, evs in lines:
            if lname == LAUNCHES:
                launches.extend(evs)
                continue
            mine: dict[str, list] = {}
            for name, start, dur in evs:
                if name == WINDOW:
                    window = (start, start + dur)
                elif name.startswith(PREFIX):
                    mine.setdefault(name[len(PREFIX):], []).append(
                        (start, start + dur))
            if mine:
                threads.append(mine)
    if window is None or device is None:
        return None
    w0, w1 = window
    busy_iv = _clip([(s, s + d) for _, s, d in device], w0, w1)
    if len(busy_iv) == 0:
        return None
    batcher: dict[str, list] = {}
    decode: list = []
    decode_s: dict[str, float] = {}
    for th in threads:
        if any(p in th for p in BATCHER):
            for p, iv in th.items():
                batcher.setdefault(p, []).extend(iv)
        else:
            for p, iv in th.items():
                c = _clip(iv, w0, w1)
                decode_s[p] = decode_s.get(p, 0.0) + _total(c) * 1e-9
                if p in DECODE:
                    decode.extend(map(tuple, c))
    if not batcher:
        return None
    b_iv = {p: _clip(iv, w0, w1) for p, iv in batcher.items()}
    dec_iv = _clip(decode, w0, w1)
    # elementary segments between every boundary; each is classified by
    # its midpoint
    edges = np.unique(np.concatenate(
        [[w0, w1], busy_iv.ravel(), dec_iv.ravel()]
        + [iv.ravel() for iv in b_iv.values()]))
    mid = (edges[:-1] + edges[1:]) / 2
    seg = np.diff(edges)
    idle = ~_inside(busy_iv, mid)
    decoding = _inside(dec_iv, mid)
    label = np.full(len(mid), "none", dtype=object)
    for p in sorted(b_iv, key=lambda p: p == "batch"):  # innermost first
        label[_inside(b_iv[p], mid) & (label == "none")] = p
    part = np.full(len(mid), "other", dtype=object)
    part[label == "none"] = "none"
    part[np.isin(label, DISPATCH)] = "dispatch"
    part[label == "sync"] = "sync"
    part[label == "batch"] = "batch"
    part[np.isin(label, DECODE)] = "decode"
    waiting = np.isin(label, BATCHER)
    part[waiting & decoding] = "decode"
    part[waiting & ~decoding] = "wait"
    idle_s = {p: float(seg[idle & (part == p)].sum()) * 1e-9 for p in PARTS}
    gaps = []
    edges_idle = np.concatenate([[w0], busy_iv.ravel(), [w1]]).reshape(-1, 2)
    for a, b in sorted((g for g in edges_idle if g[1] > g[0]),
                       key=lambda g: g[0] - g[1])[:top]:
        inn = (mid > a) & (mid < b)
        cover: dict[str, float] = {}
        for lab, s in zip(label[inn], seg[inn]):
            cover[lab] = cover.get(lab, 0.0) + s
        dec = float(seg[inn & decoding].sum())
        gaps.append([(b - a) * 1e-9, max(cover, key=cover.get),
                     dec / (b - a)])
    ops = _attribute(device, modules, launches, scopes, w0, w1)
    return Phases(
        window_s=(w1 - w0) * 1e-9,
        busy_s=_total(busy_iv) * 1e-9,
        idle_s=idle_s,
        batcher_s={p: _total(iv) * 1e-9 for p, iv in b_iv.items()},
        decode_s=decode_s,
        gaps=gaps,
        collective_exposed_s=exposed_collective_s(chips, w0, w1),
        **ops,
    )


def _instruction(op_name: str) -> str:
    m = _INSTR.match(op_name)
    return m.group(1) if m else op_name


def exposed_collective_s(chips, w0, w1) -> float | None:
    """Mean over `chips` (each a list of (op name, start_ns, duration_ns))
    of the seconds inside [w0, w1] in which a collective op runs on that
    chip and no other op does. An op whose event contains a collective's
    is its caller (a `while` or call around it, on a TPU), not work beside
    it. None where no collective ran inside the window on any chip."""
    exposed, seen = [], False
    for ops in chips:
        coll, other = [], []
        for name, s, d in ops:
            is_coll = _COLLECTIVE.search(_instruction(name))
            (coll if is_coll else other).append((s, s + d))
        c = np.asarray(coll, np.float64).reshape(-1, 2)
        o = np.asarray(other, np.float64).reshape(-1, 2)
        if len(c) and len(o):
            c = c[np.argsort(c[:, 0], kind="stable")]
            lo = np.searchsorted(c[:, 0], o[:, 0], side="left")
            hi = np.searchsorted(c[:, 0], o[:, 1], side="right")
            caller = np.zeros(len(o), bool)
            for k in np.flatnonzero(hi > lo):
                caller[k] = bool((c[lo[k]:hi[k], 1] <= o[k, 1]).any())
            o = o[~caller]
        c, o = _clip(c, w0, w1), _clip(o, w0, w1)
        seen = seen or len(c) > 0
        both = _total(_union(np.concatenate([c, o])))
        exposed.append(both - _total(o))  # |C| - |C and O|
    return float(np.mean(exposed)) * 1e-9 if seen else None


def _outermost(device, w0, w1) -> list:
    """The ops inside the window that no other op contains: on a TPU a
    `while` op's event spans the events of its body's ops."""
    out, end = [], -np.inf
    for name, s, d in sorted(device, key=lambda ev: (ev[1], -ev[2])):
        a, b = max(s, w0), min(s + d, w1)
        if b <= a or s + d <= end:
            continue
        out.append((name, s, a, b))
        end = s + d
    return out


def _link(modules, launches, w0, w1) -> list:
    """(start, end, trace name, op_scopes() key or None) of each module run
    inside the window: the last launch before it, of a module of the same
    name. "Before" allows LINK_SLACK_NS: host and device clocks agree only
    to about a tenth of a millisecond (on a v5e a module started 67 us
    before its launch's annotation), while the batcher's next launch comes
    only after it has synced on this module's flags, tens of ms later."""
    order = sorted(launches, key=lambda ev: ev[1])
    at = np.asarray([ev[1] for ev in order], np.float64)
    out = []
    for name, s, d in sorted(modules, key=lambda ev: ev[1]):
        if s + d <= w0 or s >= w1:
            continue
        i = int(np.searchsorted(at, s + LINK_SLACK_NS, side="right")) - 1
        key = order[i][0] if i >= 0 else None
        if key is not None and key.split("(")[0] != name.split("(")[0]:
            key = None
        out.append((s, s + d, name, key))
    return out


def _attribute(device, modules, launches, scopes, w0, w1) -> dict:
    """Device time by plan operator: each outermost op inside the window
    is looked up as (executable of the module run around it, HLO
    instruction)."""
    mods = _link(modules, launches, w0, w1)
    starts = np.asarray([m[0] for m in mods], np.float64)
    per_op: dict[tuple, list] = {}
    by_scope: dict[str, float] = {}
    for name, s, a, b in _outermost(device, w0, w1):
        i = int(np.searchsorted(starts, s, side="right")) - 1
        mod = mods[i][3] if i >= 0 and s < mods[i][1] else None
        instr = _instruction(name)
        scope = None
        if scopes is not None and mod in scopes:
            scope = scopes[mod].get(instr, "")
            by_scope[scope] = by_scope.get(scope, 0.0) + (b - a) * 1e-9
        per_op.setdefault((mod, instr, scope), [name, 0.0])[1] += b - a
    top = sorted(per_op.items(), key=lambda kv: -kv[1][1])[:10]
    known = [m[3] for m in mods if scopes is not None and m[3] in scopes]
    unscoped = sum(1 for k in known if not any(scopes[k].values()))
    out = {
        "top_ops": [[v[0][:NAME_CHARS], v[1] * 1e-9, k[2]]
                    for k, v in top],
        "modules": len(mods),
        "modules_known": len(known),
        "modules_unscoped": unscoped,
        "module_keys": sorted({(m[2], m[3]) for m in mods
                               if m[3] is not None}),
        "scope_s": None, "scoped_s": None, "join_s": None,
    }
    if not known or unscoped:
        return out
    scoped = {k: v for k, v in sorted(by_scope.items()) if k}
    out["scope_s"] = scoped
    out["scoped_s"] = sum(scoped.values())
    out["join_s"] = sum(v for k, v in scoped.items() if k.startswith("join"))
    return out
