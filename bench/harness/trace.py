"""Reduction of a profiler trace (``.xplane.pb``) to device times.

Reads the trace with ``jax.profiler.ProfileData``.  Each device plane
(``/device:TPU:<n>``) has a line ``XLA Ops`` of the operations the device
ran, each event named by its HLO instruction's text.  Only that line is
read, on every device: the profiler gives some devices an ``Async XLA
Ops`` line of asynchronous operations in flight and others none, so a
time that read it would mean one thing on one device and another on the
next.  A collective's time is thus the time its start and done operations
hold the device, not the time its transfer is in flight.  An operation that holds others of its line (a
loop, a call) counts as busy time only; every other one is classed as a
collective, a copy or compute by its opcode.  Host spans
that the harness writes with ``jax.profiler.TraceAnnotation`` (names that
start with ``bench.``) sit on the host plane, on the same clock: the
``bench.window`` span bounds the measured window, ``bench.product`` spans
count the products, and an idle gap on a device is named by the innermost
host span around its middle.

Times are unions of intervals clipped to the window, so operations that
nest or overlap are not counted twice.
"""
from __future__ import annotations

import dataclasses
import re

WINDOW_SPAN = "bench.window"
PRODUCT_SPAN = "bench.product"
SPAN_PREFIX = "bench."
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINES = ("XLA Ops",)
# An event's name is the HLO instruction's text, "%name = shape opcode(...)",
# or on some platforms the bare name.
_HLO = re.compile(r"^%?(?P<name>[^\s=]+)\s*=\s*(?P<rest>.*)$", re.S)
_OPCODE = re.compile(r"(?:^|[\s)}\]])([a-z][a-z0-9_-]*)\(")
_COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast|send|recv|ragged-all-to-all)(-start|-done)?"
    r"([.-]|$)")
_COPY = re.compile(r"^(copy|copy-start|copy-done)([.-]|$)")


def parse(text: str) -> tuple:
    """``(name, opcode)`` of an op event; both are the bare name when the
    event is not named by its HLO text."""
    m = _HLO.match(text)
    if not m:
        return text, text
    code = _OPCODE.search(m.group("rest"))
    return m.group("name"), code.group(1) if code else m.group("name")


def classify(text: str) -> str:
    """``"collective"``, ``"copy"`` or ``"compute"`` for an op event."""
    code = parse(text)[1]
    if _COLLECTIVE.match(code):
        return "collective"
    if _COPY.match(code):
        return "copy"
    return "compute"


def union(intervals) -> list:
    """Sorted, disjoint ``(start, end)`` intervals covering the input."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a, b) -> list:
    """Parts of the disjoint sorted intervals ``a`` not covered by ``b``."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


@dataclasses.dataclass
class Device:
    """One device's time inside the window, in seconds."""
    plane: str
    busy_s: float
    compute_s: float
    collective_s: float
    collective_exposed_s: float
    ops: dict                # op name -> seconds
    gaps: list               # idle (start_ns, end_ns) inside the window


@dataclasses.dataclass
class Summary:
    window_s: float
    n_products: int
    devices: list
    idle_by_span: dict       # host span name -> idle seconds, mean/device

    def top_ops(self, n: int = 10) -> list:
        """``[name, seconds]`` of the ops that took most time, mean over
        devices."""
        tot = {}
        for d in self.devices:
            for k, v in d.ops.items():
                tot[k] = tot.get(k, 0.0) + v / len(self.devices)
        return [[k, v] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int = 10) -> list:
        return [[k, v] for k, v in sorted(self.idle_by_span.items(),
                                          key=lambda kv: -kv[1])[:n]]


def _host_spans(profile) -> list:
    spans = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.start_ns, ev.end_ns, ev.name))
    return spans


def _span_at(spans, t: float) -> str:
    inside = [(e - s, name) for s, e, name in spans if s <= t < e]
    return min(inside)[1] if inside else "no span"


def reduce(path: str, device_ids=None) -> Summary:
    """Reduce the trace at ``path`` over the devices ``device_ids`` (all
    device planes when ``None``)."""
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(path)
    spans = _host_spans(profile)
    windows = [(s, e) for s, e, name in spans if name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span in {path}, "
                         f"found {len(windows)}")
    lo, hi = windows[0]
    n_products = sum(1 for s, e, name in spans
                     if name == PRODUCT_SPAN and s >= lo and e <= hi)
    devices = []
    idle = {}
    for plane in profile.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if not m or (device_ids is not None
                     and int(m.group(2)) not in device_ids):
            continue
        by_class = {"compute": [], "collective": [], "copy": [],
                    "container": []}
        ops = {}
        for line in plane.lines:
            if line.name not in OPS_LINES:
                continue
            events = sorted(((max(ev.start_ns, lo), min(ev.end_ns, hi),
                              ev.name) for ev in line.events),
                            key=lambda ev: (ev[0], -ev[1]))
            events = [ev for ev in events if ev[1] > ev[0]]
            for k, (s, e, text) in enumerate(events):
                # An op with another op of its line inside it (a loop, a
                # call) is busy time but no class of its own: its leaves
                # carry the class.
                nested = k + 1 < len(events) and events[k + 1][0] < e
                by_class["container" if nested else classify(text)].append(
                    (s, e))
                name = parse(text)[0]
                ops[name] = ops.get(name, 0.0) + (e - s) * 1e-9
        comp = union(by_class["compute"])
        coll = union(by_class["collective"])
        busy = union(comp + coll + by_class["copy"]
                     + by_class["container"])
        gaps = subtract([(lo, hi)], busy)
        for s, e in gaps:
            name = _span_at(spans, (s + e) / 2)
            idle[name] = idle.get(name, 0.0) + (e - s) * 1e-9
        devices.append(Device(
            plane=plane.name, busy_s=length(busy) * 1e-9,
            compute_s=length(comp) * 1e-9,
            collective_s=length(coll) * 1e-9,
            collective_exposed_s=length(subtract(coll, comp)) * 1e-9,
            ops=ops, gaps=gaps))
    if not devices:
        raise ValueError(f"no device plane with op lines in {path}")
    return Summary(window_s=(hi - lo) * 1e-9, n_products=n_products,
                   devices=devices,
                   idle_by_span={k: v / len(devices) for k, v in idle.items()})
