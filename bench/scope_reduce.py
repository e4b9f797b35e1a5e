"""Device time by the program's own scope names, from a profiler trace
(``.xplane.pb``).

The engine runs each launch kind under a ``jax.named_scope`` from one
vocabulary (``repro.obs.trace.DEVICE_SCOPES``).  On the chip each device
op's event metadata carries a ``tf_op`` stat, the op's HLO ``op_name``:
``jit(run_pl)/stage_a.window/while/body/closed_call/pallas_call``.  An
op's scope is the innermost vocabulary name among the ``/`` parts of that
name (the first of several names joined by ``;``); an op under none counts
as :data:`UNSCOPED`.  ``jax.profiler.ProfileData`` gives the stats of
events but not those of event metadata, so this module reads the
protobuf wire format itself: XSpace -> XPlane -> lines and
``event_metadata``, stat names from ``stat_metadata``.

Seconds are own times (``trace_reduce.own_times``: an op less the ops
nested in it on the ``XLA Ops`` line, so a ``while`` does not swallow its
body), clipped to the ``bench.window`` host span, averaged over the chips.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from pathlib import Path

from bench.trace_reduce import (OPS_LINE, WINDOW_SPAN, _DEVICE_PLANE,
                                find_xplane, op_label, own_times)

try:
    from repro.obs.trace import DEVICE_SCOPES
except ImportError:       # a program that names no device scopes
    DEVICE_SCOPES = ()

UNSCOPED = "unscoped"
TF_OP = "tf_op"
# where bench/run.py keeps a traced run's profile, one directory per cell
TRACE_ROOT = Path(__file__).resolve().parents[1] / ".bench_trace"


@dataclasses.dataclass
class ScopeSummary:
    window_s: float
    devices: int
    scopes: dict         # {scope or UNSCOPED: own seconds}, mean over chips
    ops: dict            # {op label (trace_reduce.op_label): own seconds}


# ------------------------------------------------------ protobuf wire format
def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf):
    """``(field number, value)`` of one message: an int for varints, a
    ``memoryview`` for length-delimited fields, raw bytes for fixed."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def _stat_value(stat, names: dict):
    """An ``XStat``'s value: ``str_value`` (5), or ``ref_value`` (7), the
    id of a ``stat_metadata`` entry whose name is the string."""
    for f, v in _fields(stat):
        if f == 5:
            return _text(v)
        if f == 7:
            return names.get(v)
    return None


def _plane(buf) -> dict:
    """One XPlane: its name, raw lines, and per event-metadata id the
    ``(name, tf_op)`` pair."""
    name, lines, meta, stat_names = "", [], [], {}
    for f, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 3:
            lines.append(v)
        elif f in (4, 5):                  # map entries: key 1, value 2
            entry = dict(_fields(v))
            if f == 4:
                meta.append(entry.get(2, b""))
            else:
                sm = dict(_fields(entry.get(2, b"")))
                stat_names[entry.get(1, 0)] = _text(sm.get(2, b""))
    tf_op_id = next((k for k, n in stat_names.items() if n == TF_OP), None)
    events = {}
    for m in meta:
        mid, mname, tf_op = 0, "", None
        for f, v in _fields(m):
            if f == 1:
                mid = v
            elif f == 2:
                mname = _text(v)
            elif f == 5 and tf_op_id is not None:
                stat = dict(_fields(v))
                if stat.get(1) == tf_op_id:
                    tf_op = _stat_value(v, stat_names)
        events[mid] = (mname, tf_op)
    return {"name": name, "lines": lines, "meta": events}


def _line_events(buf, want=None, only=None) -> tuple[str, list]:
    """``(line name, [(metadata id, start ns, end ns)])``, start and end
    as ``ProfileData`` gives them; only ids in ``want`` when given, and
    no events unless the line is named ``only`` when that is given."""
    name, t0, events = "", 0, []
    raw = []
    for f, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 3:
            t0 = v
        elif f == 4:
            raw.append(v)
    if only is not None and name != only:
        return name, []
    for ev in raw:
        mid = offset_ps = duration_ps = 0
        for f, v in _fields(ev):
            if f == 1:
                mid = v
            elif f == 2:
                offset_ps = v
            elif f == 3:
                duration_ps = v
        if want is None or mid in want:       # whole ns, as ProfileData
            start = float(t0 + offset_ps // 1000)
            events.append((mid, start, start + duration_ps // 1000))
    return name, events


def read_xspace(data: bytes) -> list[dict]:
    """The planes of a serialized XSpace (its field 1)."""
    buf = memoryview(data)
    return [_plane(v) for f, v in _fields(buf) if f == 1]


# ---------------------------------------------------------------- reduction
def scope_of(tf_op: str | None, scopes=DEVICE_SCOPES) -> str:
    """The innermost vocabulary scope in an op's ``tf_op`` name."""
    if tf_op:
        first = tf_op.split(";")[0]
        first = first.rpartition(":")[0] or first
        for part in reversed(first.split("/")):
            if part in scopes:
                return part
    return UNSCOPED


def reduce_scopes(data: bytes, scopes=DEVICE_SCOPES) -> ScopeSummary:
    """Device own seconds per scope and per op inside ``bench.window``."""
    planes = read_xspace(data)
    window = []
    for p in planes:
        if not p["name"].startswith("/host:"):
            continue
        ids = {k for k, (n, _) in p["meta"].items() if n == WINDOW_SPAN}
        for ln in p["lines"]:
            window.extend(_line_events(ln, ids)[1] if ids else [])
    if len(window) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} host span, found "
                         f"{len(window)}")
    _, w0, w1 = window[0]
    devices = [p for p in planes if _DEVICE_PLANE.match(p["name"])]
    by_scope: dict = {}
    by_op: dict = {}
    counted = 0
    for p in devices:
        ops = [events for name, events in
               (_line_events(ln, only=OPS_LINE) for ln in p["lines"])
               if name == OPS_LINE]
        if not ops:
            continue
        counted += 1
        inside = [(mid, max(s, w0), min(e, w1)) for mid, s, e in ops[0]
                  if e > w0 and s < w1]
        for mid, sec in own_times(inside).items():
            name, tf_op = p["meta"].get(mid, ("", None))
            scope = scope_of(tf_op, scopes)
            by_scope[scope] = by_scope.get(scope, 0.0) + sec
            label = op_label(name)
            by_op[label] = by_op.get(label, 0.0) + sec
    if not counted:
        raise ValueError(f"no device plane with an {OPS_LINE!r} line")
    mean = lambda d: {k: v / counted for k, v in d.items()}
    return ScopeSummary(window_s=(w1 - w0) * 1e-9, devices=counted,
                        scopes=mean(by_scope), ops=mean(by_op))


def reduce_trace_scopes(path: str) -> ScopeSummary:
    """:func:`reduce_scopes` of the ``.xplane.pb`` at ``path``."""
    with open(path, "rb") as f:
        return reduce_scopes(f.read())


@functools.lru_cache(maxsize=4)
def _cached(path: str, _mtime_ns: int) -> ScopeSummary:
    return reduce_trace_scopes(path)


def run_scopes(ctx) -> dict:
    """Own seconds per scope in this run's traced window: ``ctx.scopes``
    where the harness gives it, else the newest profile under
    :data:`TRACE_ROOT` whose window is ``ctx.trace``'s.  ``{}`` where no
    such profile is found."""
    given = getattr(ctx, "scopes", None)
    if given is not None:
        return given
    paths = []
    for cell_dir in TRACE_ROOT.glob("*"):
        try:
            paths.append(find_xplane(str(cell_dir)))
        except FileNotFoundError:
            continue
    for path in sorted(paths, key=os.path.getmtime, reverse=True):
        summary = _cached(path, os.stat(path).st_mtime_ns)
        if abs(summary.window_s - ctx.trace.window_s) <= 1e-6:
            return summary.scopes
    return {}


def per_call_ms(ctx, scope: str, calls: float) -> float | None:
    """Milliseconds of ``scope`` per call, ``None`` where the window saw
    no op of the scope or no call completed."""
    seconds = run_scopes(ctx).get(scope)
    if not seconds or not calls:
        return None
    return 1e3 * seconds / calls


__all__ = ["ScopeSummary", "UNSCOPED", "TRACE_ROOT", "read_xspace",
           "scope_of", "reduce_scopes", "reduce_trace_scopes", "run_scopes",
           "per_call_ms"]
