"""Reduce a profiler trace (``.xplane.pb``) to device busy time, the top
device operations, and the idle gaps by what the host was doing.

* Device planes are ``/device:TPU:<n>``; their operations are the events
  of the ``XLA Ops`` line.  Busy time is the union of those intervals
  inside the window, averaged over the chips; an operation's own time
  excludes the operations nested inside it on the same line.
* The window is the benchmark's ``bench.window`` host span.
* Each idle gap (window less busy union) is named after the host event
  that covers most of it, the shortest such event where several cover at
  least half of it: what the host was doing while the device waited.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
# gaps named one by one, longest first; the rest count in the total only
MAX_NAMED_GAPS = 2000


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float            # mean over the device planes
    devices: int
    device_ops: list         # [[name, seconds]], own time, top first
    idle_gaps: list          # [[host event name, seconds]], top first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {paths}")
    return paths[0]


def _events(line):
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def op_label(hlo: str) -> str:
    """``'%fusion.3 = s32[8]{0} fusion(...), kind=kLoop'`` -> ``'fusion.3
    fusion'``: a device op event is named by its whole HLO instruction."""
    name, eq, rest = hlo.partition(" = ")
    if not eq:
        return hlo
    if rest.startswith("("):       # a tuple shape: skip to its close
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        rest = rest[i + 1:]
    else:
        rest = rest.partition(" ")[2]
    return f"{name.lstrip('%')} {rest.strip().partition('(')[0]}"


def union(intervals: np.ndarray) -> np.ndarray:
    """Disjoint sorted ``(k, 2)`` union of ``(n, 2)`` intervals."""
    if intervals.size == 0:
        return intervals.reshape(0, 2)
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    reach = np.maximum.accumulate(iv[:, 1])
    starts_new = np.ones(len(iv), bool)
    starts_new[1:] = iv[1:, 0] > reach[:-1]
    first = np.flatnonzero(starts_new)
    last = np.append(first[1:] - 1, len(iv) - 1)
    return np.stack([iv[first, 0], reach[last]], axis=1)


def own_times(events) -> dict:
    """Seconds per op name, each event less the events nested in it."""
    out: dict = {}
    stack: list = []          # open events: [name, start, end, nested ns]

    def close():
        name, start, end, nested = stack.pop()
        out[name] = out.get(name, 0.0) + (end - start - nested) * 1e-9

    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= start:
            close()
        if stack:
            stack[-1][3] += end - start
        stack.append([name, start, end, 0.0])
    while stack:
        close()
    return out


def reduce_profile(pd) -> TraceSummary:
    """Reduce a ``jax.profiler.ProfileData``."""
    host, devices = [], []
    for plane in pd.planes:
        if _DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE in lines:
                devices.append(_events(lines[OPS_LINE]))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend(_events(ln))
    windows = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} host span, found "
                         f"{len(windows)}")
    if not devices:
        raise ValueError("no device plane with an 'XLA Ops' line")
    w0, w1 = windows[0]
    busy, ops, gaps = 0.0, {}, []
    for events in devices:
        inside = [(n, max(s, w0), min(e, w1)) for n, s, e in events
                  if e > w0 and s < w1]
        for name, sec in own_times(inside).items():
            label = op_label(name)
            ops[label] = ops.get(label, 0.0) + sec / len(devices)
        iv = union(np.asarray([(s, e) for _, s, e in inside], float))
        busy += float(np.sum(iv[:, 1] - iv[:, 0])) * 1e-9 / len(devices)
        edges = np.concatenate([[w0], iv.ravel(), [w1]]).reshape(-1, 2)
        gaps.append(edges[edges[:, 1] > edges[:, 0]])
    named = _name_gaps(np.concatenate(gaps), host, 1.0 / len(devices))
    top = lambda d: [[k, v] for k, v in sorted(d.items(),
                                                key=lambda kv: -kv[1])[:10]]
    return TraceSummary(window_s=(w1 - w0) * 1e-9, busy_s=busy,
                        devices=len(devices), device_ops=top(ops),
                        idle_gaps=top(named))


def _name_gaps(gaps: np.ndarray, host, weight: float) -> dict:
    host = [(n, s, e) for n, s, e in host if n != WINDOW_SPAN]
    names = np.asarray([n for n, _, _ in host], object)
    hs = np.asarray([s for _, s, _ in host], float)
    he = np.asarray([e for _, _, e in host], float)
    out: dict = {}
    order = np.argsort(gaps[:, 0] - gaps[:, 1])[:MAX_NAMED_GAPS]
    for g0, g1 in gaps[order]:
        cover = np.minimum(he, g1) - np.maximum(hs, g0)
        name = "no host event"
        if cover.size and cover.max() > 0:
            half = np.flatnonzero(cover >= 0.5 * (g1 - g0))
            best = (half[np.argmin(he[half] - hs[half])] if half.size
                    else int(np.argmax(cover)))
            name = str(names[best])
        out[name] = out.get(name, 0.0) + float(g1 - g0) * 1e-9 * weight
    return out


def reduce_trace(path: str) -> TraceSummary:
    """Reduce the ``.xplane.pb`` at ``path``."""
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path))
