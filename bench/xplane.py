"""Reduce a JAX profiler trace (`.xplane.pb`) to the device's busy time,
its heaviest operations and its idle gaps.

Busy time is the union of the intervals in which an operation ran on a
device (the "XLA Ops" line of each `/device:` plane that has one; a TPU
trace also holds device planes without ops, which are not chips), inside
the window that the harness marks with a `window` annotation on the host.
Idle gaps are the rest of that window; each is named by what covers most
of it on the host: one of the harness annotations (`query`, `update`,
`compact`), or "no request in flight" for the part none covers.
"""
from __future__ import annotations

import dataclasses
import glob
import os

import numpy as np

OPS_LINES = ("XLA Ops",)
ANNOTATIONS = ("query", "update", "compact")
WINDOW = "window"
NO_REQUEST = "no request in flight"


@dataclasses.dataclass
class Reduced:
    busy_s: float  # mean over devices of the union of op intervals
    window_s: float
    n_devices: int
    device_ops: list  # [[name, seconds]], heaviest first, summed over devices
    idle_gaps: list  # [[name, seconds]], longest first
    n_ops: int

    @property
    def idle_share(self) -> float:
        return max(0.0, 1.0 - self.busy_s / self.window_s)


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge (start, end) intervals; returns disjoint sorted intervals."""
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    group = np.cumsum(new) - 1
    stops = np.zeros(int(new.sum()))
    np.maximum.at(stops, group, iv[:, 1])
    return np.stack([starts, stops], axis=1)


def _overlap(a0, a1, iv: np.ndarray) -> float:
    if len(iv) == 0:
        return 0.0
    lo = np.maximum(iv[:, 0], a0)
    hi = np.minimum(iv[:, 1], a1)
    return float(np.clip(hi - lo, 0, None).sum())


def find_xplane(trace_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def reduce_planes(planes, top: int = 10) -> Reduced | None:
    """`planes`: iterable of (plane name, [(line name, [(event name,
    start_ns, duration_ns)])]). Returns None where no device operation
    ran inside the window."""
    window = None
    host: dict[str, list] = {a: [] for a in ANNOTATIONS}
    devices = []
    for pname, lines in planes:
        if pname.startswith("/device:"):
            if any(lname in OPS_LINES for lname, _ in lines):
                devices.append([ev for lname, evs in lines
                                if lname in OPS_LINES for ev in evs])
            continue
        for _, evs in lines:
            for name, start, dur in evs:
                if name == WINDOW:
                    window = (start, start + dur)
                elif name in host:
                    host[name].append((start, start + dur))
    if window is None or not devices:
        return None
    w0, w1 = window
    busy, per_op, n_ops, unions = [], {}, 0, []
    for ops in devices:
        iv = []
        for name, start, dur in ops:
            a, b = max(start, w0), min(start + dur, w1)
            if b <= a:
                continue
            iv.append((a, b))
            per_op[name] = per_op.get(name, 0.0) + (b - a)
            n_ops += 1
        u = _union(np.asarray(iv, np.float64).reshape(-1, 2))
        unions.append(u)
        busy.append(float((u[:, 1] - u[:, 0]).sum()))
    if n_ops == 0:
        return None
    host_iv = {k: _union(np.asarray(v, np.float64).reshape(-1, 2))
               for k, v in host.items()}
    any_iv = _union(np.concatenate([iv for iv in host_iv.values()]))
    # idle gaps of the first device; busy time above is a mean over all
    u = unions[0]
    edges = np.concatenate([[w0], u.ravel(), [w1]]).reshape(-1, 2)
    gaps = [(a, b) for a, b in edges if b > a]
    named = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        cover = {k: _overlap(a, b, iv) for k, iv in host_iv.items()}
        cover[NO_REQUEST] = (b - a) - _overlap(a, b, any_iv)
        named.append([max(cover, key=cover.get), (b - a) * 1e-9])
    ops_sorted = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return Reduced(
        busy_s=float(np.mean(busy)) * 1e-9,
        window_s=(w1 - w0) * 1e-9,
        n_devices=len(devices),
        device_ops=[[k, v * 1e-9] for k, v in ops_sorted],
        idle_gaps=named,
        n_ops=n_ops,
    )


def load(path: str):
    """The planes of an `.xplane.pb`, in the form reduce_planes takes."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            lines.append((line.name, [(ev.name, float(ev.start_ns),
                                       float(ev.duration_ns))
                                      for ev in line.events]))
        out.append((plane.name, lines))
    return out


def reduce_file(path: str, top: int = 10) -> Reduced | None:
    return reduce_planes(load(path), top)
