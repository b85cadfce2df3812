"""Span-based execution tracing with Chrome-trace (Perfetto) export.

Tracing is off by default.  When off, ``span()`` returns one shared no-op
context manager — no allocation, no clock read — so instrumented hot paths
(plan calls, serving decode steps) pay a single boolean check.  When on,
each span does two things:

* it enters a ``jax.profiler.TraceAnnotation`` of the same name, so a span
  taken while a profiler session records sits on the host plane of the
  ``.xplane.pb``, on the same clock as the device's operations;
* it buffers a Chrome-trace "complete" event (``ph: "X"``) with
  microsecond ``ts``/``dur``, the recording thread's id, and any keyword
  attributes under ``args``.  ``ts`` is read from the clock the profiler
  stamps its own host events with, the wall clock in nanoseconds since the
  Unix epoch (``time.time_ns``): an ``.xplane.pb`` stores its times from
  ``profile_start_time`` (a stat of its ``Task Environment`` plane) on, so
  ``ts * 1e3 - profile_start_time`` is the event's start in the trace.

Nesting needs no explicit parent plumbing: Perfetto reconstructs the stack
per-thread from interval containment, and we additionally record the
thread-local depth for the textual viewer.

Drift recording (``MatmulPlan.__call__`` blocking on every product to set
its measured time beside the cost model's prediction) has a switch of its
own, ``enable(drift=True)``: spans alone never change what the program
does.

Timing discipline helpers live here too: ``sync_elapsed`` (block until a
jax pytree is ready, then read the clock) and ``timed`` (time a thunk with
a trailing block) — the only sanctioned ways to wall-time jax work, which
``tools/check_api.py`` enforces repo-wide.
"""
from __future__ import annotations

import json
import threading
import time
from typing import Dict, List, Optional

# Trace-buffer cap: ~100k spans bounds memory for runaway traced loops;
# drops are counted and surfaced in export metadata.
_MAX_EVENTS = 100_000


class _State:
    def __init__(self):
        self.enabled = False
        self.lock = threading.Lock()
        self.drift = False
        self.annotation = None     # jax.profiler.TraceAnnotation, at enable()
        self.events: List[Dict] = []
        self.dropped = 0


_STATE = _State()
_TLS = threading.local()


class _NullSpan:
    """Shared do-nothing context manager returned while tracing is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def note(self, **attrs):
        pass


_NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("name", "args", "_start", "_depth", "_ann")

    def __init__(self, name: str, args: Dict):
        self.name = name
        self.args = args
        self._start = 0
        self._depth = 0
        self._ann = _STATE.annotation(name)

    def note(self, **attrs) -> None:
        """Attach attributes discovered mid-span (e.g. cache hit/miss)."""
        self.args.update(attrs)

    def __enter__(self):
        depth = getattr(_TLS, "depth", 0)
        _TLS.depth = depth + 1
        self._depth = depth
        self._ann.__enter__()
        self._start = time.time_ns()
        return self

    def __exit__(self, *exc):
        end = time.time_ns()
        self._ann.__exit__(*exc)
        _TLS.depth = self._depth
        ev = {
            "ph": "X",
            "name": self.name,
            "cat": "repro",
            "ts": self._start / 1e3,
            "dur": (end - self._start) / 1e3,
            "pid": 0,
            "tid": threading.get_ident() % 2**31,
            "args": dict(self.args, depth=self._depth),
        }
        with _STATE.lock:
            if len(_STATE.events) < _MAX_EVENTS:
                _STATE.events.append(ev)
            else:
                _STATE.dropped += 1
        return False


def enable(clear: bool = False, drift: bool = False) -> None:
    """Turn tracing on; ``clear=True`` also drops buffered events.

    ``drift=True`` also turns on drift recording: every plan call then
    blocks on its result and records its measured time beside the cost
    model's prediction (``repro.obs.drift``).  Spans alone never block.
    """
    from jax.profiler import TraceAnnotation   # deferred: see the package

    if clear:
        clear_trace()
    _STATE.annotation = TraceAnnotation
    _STATE.drift = drift
    _STATE.enabled = True


def disable() -> None:
    """Turn tracing and drift recording off."""
    _STATE.enabled = False
    _STATE.drift = False


def enabled() -> bool:
    return _STATE.enabled


def drift_enabled() -> bool:
    """Whether plan calls record drift (``enable(drift=True)``)."""
    return _STATE.enabled and _STATE.drift


def span(name: str, **attrs):
    """Context manager recording a Chrome-trace span while tracing is on.

    Returns a shared inert object when tracing is off — safe (and ~free)
    to leave on hot paths unconditionally.
    """
    if not _STATE.enabled:
        return _NULL_SPAN
    return _Span(name, attrs)


def events() -> List[Dict]:
    """Copy of the buffered events (oldest first)."""
    with _STATE.lock:
        return list(_STATE.events)


def clear_trace() -> None:
    with _STATE.lock:
        _STATE.events = []
        _STATE.dropped = 0


def export_trace(path: Optional[str] = None) -> Dict:
    """Render buffered spans as a Chrome-trace JSON object.

    The result loads directly in Perfetto (ui.perfetto.dev) or
    chrome://tracing.  Every event carries the keys
    ``ph``/``ts``/``dur``/``name``/``pid``/``tid``.  When ``path`` is
    given the object is also written there as JSON.
    """
    with _STATE.lock:
        evs = list(_STATE.events)
        dropped = _STATE.dropped
    obj = {
        "traceEvents": evs,
        "displayTimeUnit": "ms",
        "otherData": {"dropped_events": dropped, "source": "repro.obs"},
    }
    if path is not None:
        with open(path, "w") as f:
            json.dump(obj, f)
    return obj


REQUIRED_EVENT_KEYS = ("ph", "ts", "dur", "name", "pid", "tid")


def validate_trace(obj: Dict) -> List[str]:
    """Return a list of schema problems ([] means valid Chrome trace)."""
    problems: List[str] = []
    evs = obj.get("traceEvents")
    if not isinstance(evs, list):
        return ["traceEvents missing or not a list"]
    for i, ev in enumerate(evs):
        for k in REQUIRED_EVENT_KEYS:
            if k not in ev:
                problems.append(f"event {i} missing key {k!r}")
        if "ts" in ev and not isinstance(ev["ts"], (int, float)):
            problems.append(f"event {i} ts not numeric")
        if "dur" in ev and not isinstance(ev["dur"], (int, float)):
            problems.append(f"event {i} dur not numeric")
    return problems


def sync_elapsed(t0: float, tree) -> float:
    """Block until ``tree``'s arrays are ready, return seconds since t0.

    The only honest way to wall-time async-dispatched jax work: without
    the block the stop-clock reads dispatch time, not execution time.
    """
    import jax  # deferred: obs must be importable before platform flags

    jax.block_until_ready(tree)
    return time.perf_counter() - t0


def timed(fn, repeats: int = 1, warmup: int = 0) -> float:
    """Mean wall seconds per call of ``fn()``, blocking on its result.

    Replaces the per-bench ``_timed`` helpers that read ``perf_counter``
    around un-blocked jax calls (the async-dispatch smear).
    """
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(max(1, repeats)):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / max(1, repeats)
