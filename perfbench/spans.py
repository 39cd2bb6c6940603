"""The program's own spans, and the phase of each device op, in the trace
of a traced run (``harness.WORK_DIR/trace/<cell>``).

The program opens host spans whose names start with :data:`PREFIX`
(``densest.solve``, ``densest.ladder.upload``, ...; ``jax.profiler.
TraceAnnotation`` in ``repro.core.api``) and names each phase of the pass
body with a ``jax.named_scope`` under ``peel`` (``repro.core.engine.
run_peel``).  A TPU trace carries that name path on each device op as its
framework-name stat :data:`FRAMEWORK_STAT`, on the op's event or on its
metadata.  :func:`read` returns

    {"window": [start_ns, dur_ns] or None,
     "spans": [[name, start_ns, dur_ns, parent], ...],
     "ops": {plane: [[phase, start_ns, dur_ns], ...], ...}}

``parent`` is the index of the innermost span of the program that encloses
the span on its thread, or -1.  ``phase`` is one of :data:`PHASES`, or
None for an op outside the pass body's phases; container ops (``while``,
``conditional``, ``call``) are left out, since the ops nested in them are
traced on their own.  A program without such spans or scopes gives empty
lists, and the readers built on this return None.

The profiler's Python bindings do not expose an event's metadata stats,
so the device planes are decoded here from the protobuf wire format
(:func:`_fields`); the host spans come from ``jax.profiler.ProfileData``
as in ``perfbench/trace.py``.
"""

from __future__ import annotations

import functools
import glob
import os
import re

import jax

from perfbench import harness, trace

PREFIX = "densest."
SCOPE = "peel"
PHASES = ("edge_filter", "degree_count", "best_set", "removal")
FRAMEWORK_STAT = "tf_op"
CONTAINERS = ("while", "conditional", "call")


def _varint(buf, i: int):
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of each field of one serialized message; a
    length-delimited value is a memoryview of its bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire == 1:
            v, i = buf[i:i + 8], i + 8
        elif wire == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} in a trace")
        yield field, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


# XSpace.planes = 1; XPlane: name = 2, lines = 3, event_metadata = 4,
# stat_metadata = 5; XLine: name = 2, timestamp_ns = 3, events = 4; XEvent:
# metadata_id = 1, offset_ps = 2, duration_ps = 3, stats = 4;
# XEventMetadata: name = 2, stats = 5; XStat: metadata_id = 1,
# str_value = 5, ref_value = 7; map entries: key = 1, value = 2.


def _stats(buf_list):
    """``{stat metadata id: str or ("ref", id)}`` of a message's string
    stats (the only kind read here)."""
    out = {}
    for buf in buf_list:
        sid = val = None
        for f, v in _fields(buf):
            if f == 1:
                sid = v
            elif f == 5:
                val = _text(v)
            elif f == 7:
                val = ("ref", v)
        if sid is not None and val is not None:
            out[sid] = val
    return out


def _device_plane(buf):
    """Ops of one ``/device:TPU:<n>`` plane as ``[[hlo name, framework
    name, start_ns, dur_ns], ...]`` from its ``XLA Ops`` line."""
    lines, ev_meta, stat_names = [], {}, {}
    for f, v in _fields(buf):
        if f == 3:
            lines.append(v)
        elif f in (4, 5):
            key = value = None
            for g, w in _fields(v):
                if g == 1:
                    key = w
                elif g == 2:
                    value = w
            if value is None:
                continue
            if f == 5:
                stat_names[key] = next(
                    (_text(w) for g, w in _fields(value) if g == 2), "")
            else:
                name, stats = "", []
                for g, w in _fields(value):
                    if g == 2:
                        name = _text(w)
                    elif g == 5:
                        stats.append(w)
                ev_meta[key] = (name, _stats(stats))
    want = [k for k, s in stat_names.items() if s == FRAMEWORK_STAT]

    def framework(stats):
        for k in want:
            val = stats.get(k)
            if isinstance(val, tuple):
                return stat_names.get(val[1])
            if val is not None:
                return val
        return None

    ops = []
    for line in lines:
        name, ts, events = "", 0, []
        for f, v in _fields(line):
            if f == 2:
                name = _text(v)
            elif f == 3:
                ts = v
            elif f == 4:
                events.append(v)
        if name != trace._OPS_LINE:
            continue
        for ev in events:
            mid, off, dur, stats = 0, 0, 0, []
            for f, v in _fields(ev):
                if f == 1:
                    mid = v
                elif f == 2:
                    off = v
                elif f == 3:
                    dur = v
                elif f == 4:
                    stats.append(v)
            hlo, meta_stats = ev_meta.get(mid, ("", {}))
            fw = framework(_stats(stats)) or framework(meta_stats)
            ops.append([hlo, fw, ts + off / 1000, dur / 1000])
    return ops


def _device_planes(data: bytes):
    out = {}
    for f, plane in _fields(memoryview(data)):
        if f != 1:
            continue
        name = next((_text(v) for g, v in _fields(plane) if g == 2), "")
        if trace._DEVICE_PLANE.match(name):
            out[name] = _device_plane(plane)
    return out


def is_container(hlo_name: str) -> bool:
    """Whether a device op is a ``while``, ``conditional`` or ``call``,
    whose time is that of the ops nested in it."""
    head = hlo_name.lstrip("%").split(" ", 1)[0].split("=", 1)[0]
    return head.split(".", 1)[0] in CONTAINERS


def _unwrap(part: str) -> str:
    """A scope name without the transforms JAX wraps it in: ``vmap(peel)``
    (the pass body of a batched sweep) reads ``peel``."""
    while True:
        m = _WRAPPED.fullmatch(part)
        if m is None:
            return part
        part = m.group(1)


_WRAPPED = re.compile(r"[A-Za-z_][\w.]*\((.*)\)")


def phase_of(framework_name) -> str | None:
    """The pass-body phase of a ``named_scope`` path such as
    ``jit(f)/peel/while/body/edge_filter/gather:`` (a TPU trace appends
    ``:<op type>``; under ``vmap`` the scope reads ``vmap(peel)``): the
    first of :data:`PHASES` after a ``peel`` component."""
    if not framework_name:
        return None
    parts = [_unwrap(p) for p in framework_name.split(":", 1)[0].split("/")]
    if SCOPE not in parts:
        return None
    for part in parts[parts.index(SCOPE) + 1:]:
        if part in PHASES:
            return part
    return None


def _nest(line_spans):
    """Parent index of each span of one thread (spans nest on a thread)."""
    order = sorted(range(len(line_spans)),
                   key=lambda i: (line_spans[i][1], -line_spans[i][2]))
    parents, stack = [-1] * len(line_spans), []
    for i in order:
        _, start, dur = line_spans[i]
        while stack and start >= line_spans[stack[-1]][1] + line_spans[stack[-1]][2]:
            stack.pop()
        parents[i] = stack[-1] if stack else -1
        stack.append(i)
    return parents


def read(log_dir: str) -> dict:
    """The program's spans and the phased device ops of the one trace
    under ``log_dir``; the readers of one run share one parse."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found {paths}")
    return _read_file(paths[0], os.path.getmtime(paths[0]))


@functools.lru_cache(maxsize=1)
def _read_file(path: str, mtime: float) -> dict:
    with open(path, "rb") as f:
        return parse(f.read())


def parse(data: bytes) -> dict:
    """:func:`read` on the bytes of one ``.xplane.pb``."""
    window, spans = None, []
    for plane in jax.profiler.ProfileData.from_serialized_xspace(data).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            mine = []
            for ev in line.events:
                if ev.name == trace.WINDOW_SPAN:
                    window = [ev.start_ns, ev.duration_ns]
                elif ev.name.startswith(PREFIX):
                    mine.append([ev.name, ev.start_ns, ev.duration_ns])
            base = len(spans)
            for s, parent in zip(mine, _nest(mine)):
                spans.append(s + [parent + base if parent >= 0 else -1])
    ops = {
        plane: [[phase_of(fw), start, dur] for hlo, fw, start, dur in plane_ops
                if not is_container(hlo)]
        for plane, plane_ops in _device_planes(data).items()
    }
    return {"window": window, "spans": spans, "ops": ops}


def self_ns(spans) -> list:
    """Each span's duration less the part its child spans cover."""
    out = [s[2] for s in spans]
    for name, start, dur, parent in spans:
        if parent >= 0:
            p = spans[parent]
            covered = min(start + dur, p[1] + p[2]) - max(start, p[1])
            out[parent] -= max(covered, 0)
    return out


def _inside(window, start, dur) -> bool:
    return window is not None and window[0] <= start and start + dur <= window[0] + window[1]


def span_seconds(tr: dict, keep) -> float | None:
    """Self seconds of the spans inside the window whose name ``keep``
    accepts, or None when there is none."""
    total, found = 0.0, False
    for (name, start, dur, _), own in zip(tr["spans"], self_ns(tr["spans"])):
        if keep(name) and _inside(tr["window"], start, dur):
            total, found = total + own, True
    return total / 1e9 if found else None


def phase_seconds(tr: dict, phase: str) -> float | None:
    """Device seconds of the ops of ``phase`` inside the window, averaged
    over the device planes, or None when no op carries the phase."""
    if tr["window"] is None or not tr["ops"]:
        return None
    w0, w1 = tr["window"][0], tr["window"][0] + tr["window"][1]
    total, found = 0.0, False
    for plane_ops in tr["ops"].values():
        for ph, start, dur in plane_ops:
            if ph == phase:
                found = True
                total += max(0.0, min(start + dur, w1) - max(start, w0))
    return total / len(tr["ops"]) / 1e9 if found else None


def trace_dir(ctx) -> str:
    """Where the harness writes the trace of a traced run of ``ctx``'s cell."""
    return os.path.join(harness.WORK_DIR, "trace", ctx.cell.name)
