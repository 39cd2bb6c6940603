"""The device trace of a measured window, reduced to busy time, idle gaps
and the device operations that took the most time.

A traced run wraps its window in the span :data:`WINDOW_SPAN` and each call
into the program in a span whose name starts with :data:`SPAN_PREFIX`
(``jax.profiler.TraceAnnotation``, so they share the profiler's clock with
the device).  :func:`events` pulls out of the profiler's ``.xplane.pb`` only
what the reduction reads, in a plain form that a test fixture can hold:

    {"spans": [[name, start_ns, dur_ns], ...],
     "devices": {plane_name: [[op_name, start_ns, dur_ns], ...], ...}}

and :func:`reduce` turns that into the numbers a run reports.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil

import jax

SPAN_PREFIX = "perfbench."
WINDOW_SPAN = "perfbench.window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_OPS_LINE = "XLA Ops"
TOP = 10


@contextlib.contextmanager
def capture(log_dir: str):
    """Profiles the body into ``log_dir`` (emptied first), host Python
    tracing off so that the trace costs the host little."""
    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _stat(ev, key):
    try:
        return dict(ev.stats).get(key)
    except (TypeError, ValueError):
        return None


def events(log_dir: str) -> dict:
    """Benchmark spans and per-device op intervals of the trace in
    ``log_dir``."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found {paths}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    spans, devices = [], {}
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != _OPS_LINE:
                    continue
                for ev in line.events:
                    module = _stat(ev, "hlo_module")
                    name = f"{module}/{ev.name}" if module else ev.name
                    ops.append([name, int(ev.start_ns), int(ev.duration_ns)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append([ev.name, int(ev.start_ns), int(ev.duration_ns)])
    return {"spans": spans, "devices": devices}


def _merge(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _label(spans, t: float) -> str:
    """The innermost benchmark span (the window aside) open at ``t``."""
    best = None
    for name, start, dur in spans:
        if name != WINDOW_SPAN and start <= t <= start + dur:
            if best is None or dur < best[1]:
                best = (name, dur)
    return best[0] if best else "between calls"


def reduce(ev: dict) -> dict:
    """``window_s``, ``busy_s`` (union of op intervals inside the window,
    averaged over the devices), ``device_ops`` (the ``TOP`` ops by summed
    seconds, all devices) and ``idle_gaps`` (the ``TOP`` longest idle
    stretches of the first device, each named by the span open at its
    middle)."""
    windows = [s for s in ev["spans"] if s[0] == WINDOW_SPAN]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span, found {len(windows)}")
    _, w0, wd = windows[0]
    w1 = w0 + wd
    if not ev["devices"]:
        raise RuntimeError("the trace holds no device plane")
    busy, per_op, gaps = [], {}, None
    for plane in sorted(ev["devices"]):
        clipped = []
        for name, start, dur in ev["devices"][plane]:
            a, b = max(start, w0), min(start + dur, w1)
            if b > a:
                clipped.append((a, b))
                per_op[name] = per_op.get(name, 0) + (b - a)
        merged = _merge(clipped)
        busy.append(sum(b - a for a, b in merged))
        if gaps is None:
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            gaps = [
                (edges[i + 1] - edges[i], (edges[i] + edges[i + 1]) / 2)
                for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]
            ]
    gaps.sort(key=lambda g: -g[0])
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": wd / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "device_ops": [[name, ns / 1e9] for name, ns in ops],
        "idle_gaps": [[_label(ev["spans"], mid), ns / 1e9] for ns, mid in gaps[:TOP]],
    }
