"""Reduce a ``jax.profiler`` trace to what the per-layer metrics read.

The trace is first turned into plain data, the shape of the profiler's
XSpace: ``{"planes": [{"name", "lines": [{"name", "events": [{"name",
"start_ns", "duration_ns", "stats": {...}}]}]}]}``.  :func:`xspace_dict` does that
with ``jax.profiler.ProfileData``; a test fixture is the same data as JSON.

* Device planes are named ``/device:TPU:<n>``.  Their ``XLA Ops`` line
  holds one event per operation run on the chip (an HLO op; a Pallas
  kernel is a ``custom-call``), their ``XLA Modules`` line one event per
  program run.
* The harness's own host spans (``jax.profiler.TraceAnnotation``) are the
  host events whose names start with ``bench.``; ``bench.window`` bounds
  the traced window.

Busy time is the union of a device's op intervals inside the window; the
idle share is one minus busy over the window, averaged over the devices
the run used.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Event:
    name: str
    start: int                    # ns
    end: int                      # ns


@dataclasses.dataclass
class Device:
    index: int
    ops: list
    modules: list


@dataclasses.dataclass
class Trace:
    devices: list                 # Device, one per chip used
    spans: list                   # harness host spans (Event)
    window: tuple                 # (start_ns, end_ns)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def start(trace_dir: str) -> None:
    """Start the profiler with Python-call tracing off (it would slow the
    host path being measured); the harness spans and the runtime's own
    host events stay."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def _event(ev: dict) -> Event:
    start = int(ev["start_ns"])
    return Event(ev["name"], start, start + int(ev["duration_ns"]))


def _op_name(name: str) -> str:
    """A TPU op event is named by its whole HLO instruction
    (``%fusion.12 = bf16[...] fusion(...)``): keep ``fusion.12``."""
    m = re.match(r"%?([^\s=]+)", name)
    return m.group(1) if m else name


def xspace_dict(path: str) -> dict:
    """Plain-data copy of the parts of an ``.xplane.pb`` file this module
    reads (see the module doc): the device planes' op and module lines,
    with ops under their short HLO names, and the harness's host spans.
    Stats are dropped: nothing here reads them."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        device = DEVICE_PLANE.match(plane.name) is not None
        if not device and not plane.name.startswith("/host:"):
            continue
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            short = device and line.name == OPS_LINE
            evs = [{"name": _op_name(ev.name) if short else ev.name,
                    "start_ns": ev.start_ns, "duration_ns": ev.duration_ns,
                    "stats": {}}
                   for ev in line.events
                   if device or ev.name.startswith("bench.")]
            if evs:
                lines.append({"name": line.name, "events": evs})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def reduce(space: dict, devices=None) -> Trace:
    """The window, the harness spans and each used device's op and module
    events.  ``devices``: the device ids the run used (all when None)."""
    devs, spans = [], []
    for plane in space["planes"]:
        m = DEVICE_PLANE.match(plane["name"])
        if m:
            idx = int(m.group(1))
            if devices is not None and idx not in devices:
                continue
            lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
            devs.append(Device(idx, [_event(e) for e in lines.get(OPS_LINE, [])],
                               [_event(e) for e in
                                lines.get(MODULES_LINE, [])]))
        elif plane["name"].startswith("/host:"):
            spans += [_event(e) for ln in plane["lines"]
                      for e in ln["events"] if e["name"].startswith("bench.")]
    win = [s for s in spans if s.name == WINDOW_SPAN]
    if not win:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
    window = (win[0].start, win[0].end)
    devs.sort(key=lambda d: d.index)
    return Trace(devs, [s for s in spans if s.name != WINDOW_SPAN], window)


def _clip(events, window):
    lo, hi = window
    return sorted((max(e.start, lo), min(e.end, hi)) for e in events
                  if e.end > lo and e.start < hi)


def _union(intervals):
    out = []
    for s, e in intervals:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_s(trace: Trace) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    if not trace.devices:
        return 0.0
    tot = sum(e - s for d in trace.devices
              for s, e in _union(_clip(d.ops, trace.window)))
    return tot * 1e-9 / len(trace.devices)


def idle_share(trace: Trace):
    if not trace.devices or trace.window_s <= 0:
        return None
    return 1.0 - busy_s(trace) / trace.window_s


def matched_s(trace: Trace, pattern: str, line: str = "ops") -> float:
    """Seconds of the ``line`` ("ops" or "modules") events whose name
    matches ``pattern``, summed over the devices (clipped to the
    window)."""
    rx = re.compile(pattern)
    tot = 0
    for d in trace.devices:
        tot += sum(e - s for s, e in _clip(
            [ev for ev in getattr(d, line) if rx.search(ev.name)],
            trace.window))
    return tot * 1e-9


def top_ops(trace: Trace, n: int = 10):
    """The ``n`` op names that took most device time: ``[[name, s]]``,
    with a trailing ``.<number>`` of HLO names dropped so instances of
    one op add up."""
    by: dict = {}
    for d in trace.devices:
        for ev in d.ops:
            if ev.end <= trace.window[0] or ev.start >= trace.window[1]:
                continue
            key = re.sub(r"\.\d+$", "", ev.name)
            by[key] = by.get(key, 0) + (min(ev.end, trace.window[1])
                                        - max(ev.start, trace.window[0]))
    top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in top]


def idle_gaps(trace: Trace, n: int = 10):
    """The ``n`` longest gaps with no op on a device, each named by the
    innermost harness span around its midpoint (``host`` when none):
    ``[[name, s]]``."""
    gaps = []
    for d in trace.devices:
        busy = _union(_clip(d.ops, trace.window))
        edges = [trace.window[0]] + [x for iv in busy for x in iv] \
            + [trace.window[1]]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, (s + e) // 2))
    gaps.sort(reverse=True)
    out = []
    for dur, mid in gaps[:n]:
        around = [sp for sp in trace.spans if sp.start <= mid < sp.end]
        name = (min(around, key=lambda sp: sp.end - sp.start).name
                if around else "host")
        out.append([name, dur * 1e-9])
    return out
