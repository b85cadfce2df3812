"""What the program records about itself (``repro.obs``): its counters,
read by the metric readers by name from the process's registry, and its
spans, read by ``bench/tools/setup_split.py``.

A span is a buffered Chrome-trace event (``name``, ``ts`` and ``dur`` in
microseconds, ``tid``); spans of one thread nest.  A span's self time is
its duration less the part of it that spans nested inside it, on its
thread, cover.
"""
from __future__ import annotations

from harness import trace as trace_mod


def self_s(events, name: str):
    """Summed self time of the spans called ``name``, in seconds; ``None``
    when the run recorded no such span."""
    if not events:
        return None
    mine = [e for e in events if e["name"] == name]
    if not mine:
        return None
    total = 0.0
    for e in mine:
        lo, hi = e["ts"], e["ts"] + e["dur"]
        inside = [(max(c["ts"], lo), min(c["ts"] + c["dur"], hi))
                  for c in events
                  if c is not e and c["tid"] == e["tid"]
                  and c["args"].get("depth", 0) > e["args"].get("depth", 0)
                  and c["ts"] < hi and c["ts"] + c["dur"] > lo]
        total += (hi - lo) - trace_mod.length(trace_mod.union(inside))
    return total * 1e-6


def counters():
    """The program's counters as they stand now, its registry's plain-dict
    snapshot; the program sets them as it builds a plan, traced or not."""
    from repro import obs

    return obs.registry().snapshot()


def gauge(counters, name: str):
    """The value of the gauge ``name``, whatever its labels, when the run
    set exactly one series of it; ``None`` otherwise."""
    if not counters or name not in counters:
        return None
    value = counters[name]
    if isinstance(value, dict):
        values = [v for v in value.values() if v is not None]
        if len(values) != 1:
            return None
        value = values[0]
    return value
