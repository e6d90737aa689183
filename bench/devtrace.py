"""Profiler trace of the measured window, and its reduction to metrics.

``Capture`` wraps ``jax.profiler.start_trace``/``stop_trace`` with the
Python tracer off (it would record every call of 64 client threads).
``load`` turns the written ``.xplane.pb`` into plain data:

    [{"name": plane, "lines": [{"name": line,
                                "events": [[name, start_ns, dur_ns], ...]}]}]

which is also the format of the recorded fixture the tests reduce.
``reduce`` computes, from that data alone:

* the traced window: the host span named ``WINDOW_SPAN`` that the
  harness opens around the measured window;
* device busy time: the union of the intervals of the device's op events
  inside the window (``busy_s``), and from it the idle share;
* per-kernel launches and device time, for event names matching a pattern;
* the device ops that took most time, and the longest idle gaps named by
  the innermost host span that covers each gap's middle.
"""

from __future__ import annotations

import re
from pathlib import Path

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINES = ("XLA Ops",)


class Capture:
    def __init__(self, logdir: Path):
        self.logdir = Path(logdir)

    def __enter__(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.logdir), profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import jax

        jax.profiler.stop_trace()


def load(logdir: Path) -> list[dict]:
    """Every plane, line and event of the one ``.xplane.pb`` under
    ``logdir``."""
    from jax.profiler import ProfileData

    files = sorted(Path(logdir).rglob("*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {logdir}, found "
                           f"{len(files)}")
    planes = []
    for plane in ProfileData.from_file(str(files[0])).planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name, "events": [
                [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                for ev in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def _union(intervals: list[tuple[float, float]]) -> list[list[float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(s: float, e: float, lo: float, hi: float):
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def window_bounds(planes: list[dict]) -> tuple[float, float]:
    """(start_ns, end_ns) of the host span ``WINDOW_SPAN``."""
    for plane in planes:
        if plane["name"].startswith("/host"):
            for line in plane["lines"]:
                for name, s, d in line["events"]:
                    if name == WINDOW_SPAN:
                        return s, s + d
    raise RuntimeError(f"no {WINDOW_SPAN!r} span in the trace")


def device_ops(planes: list[dict]) -> dict[str, list]:
    """Device plane name -> its op events."""
    out = {}
    for plane in planes:
        if DEVICE_PLANE.match(plane["name"]):
            out[plane["name"]] = [ev for line in plane["lines"]
                                  if line["name"] in OP_LINES
                                  for ev in line["events"]]
    return out


def reduce(planes: list[dict], kernels: dict[str, str],
           top: int = 10) -> dict:
    """Window, busy time, kernel time and the breakdown.

    ``kernels`` maps a kernel's label to a regular expression that its
    op events' names match. Returns ``window_s``, ``busy_s`` (mean over
    the devices that ran an op), ``devices``, ``kernels`` (label ->
    ``{"launches", "seconds"}``), ``device_ops`` and ``idle_gaps``."""
    lo, hi = window_bounds(planes)
    per_dev = {name: [(s, s + d, n) for n, s, d in evs]
               for name, evs in device_ops(planes).items()}
    per_dev = {k: v for k, v in per_dev.items() if v}
    busy, op_time, gaps = [], {}, []
    kern = {label: {"launches": 0, "seconds": 0.0} for label in kernels}
    pats = {label: re.compile(p) for label, p in kernels.items()}
    for evs in per_dev.values():
        inside = []
        for s, e, name in evs:
            c = _clip(s, e, lo, hi)
            if c is None:
                continue
            inside.append(c)
            op_time[name] = op_time.get(name, 0.0) + (c[1] - c[0]) * 1e-9
            for label, pat in pats.items():
                if pat.search(name):
                    kern[label]["launches"] += 1
                    kern[label]["seconds"] += (e - s) * 1e-9
        merged = _union(inside)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    host = [(s, s + d, n) for plane in planes
            if plane["name"].startswith("/host")
            for line in plane["lines"] for n, s, d in line["events"]
            if d > 0 and n != WINDOW_SPAN]
    idle = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = 0.5 * (s + e)
        cover = [(he - hs, n) for hs, he, n in host if hs <= mid <= he]
        idle.append([min(cover)[1] if cover else "no host span",
                     (e - s) * 1e-9])
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "devices": len(per_dev),
        "kernels": kern,
        "device_ops": [[n, t] for n, t in ops],
        "idle_gaps": idle,
    }
