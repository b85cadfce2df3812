#!/usr/bin/env python3
"""Print what a profiler trace holds: planes, lines, and the events that
took most time on each line, with their stats.  For reading a trace by
hand before writing a reduction against it.

    python3 bench/tools/trace_summary.py path/to/file.xplane.pb [--top 25]
"""
import argparse
import sys


def summary(path: str, top: int) -> str:
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  LINE {line.name!r} events={len(events)}")
            by_name = {}
            for ev in events:
                by_name.setdefault(ev.name, []).append(ev)
            ranked = sorted(by_name.items(),
                            key=lambda kv: -sum(e.duration_ns for e in kv[1]))
            for name, evs in ranked[:top]:
                first = evs[0]
                out.append(
                    f"    {name!r} x{len(evs)} total_ns="
                    f"{sum(e.duration_ns for e in evs):.0f} first_start_ns="
                    f"{first.start_ns:.0f} stats={list(first.stats)[:12]}")
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path")
    ap.add_argument("--top", type=int, default=25)
    args = ap.parse_args(argv)
    print(summary(args.path, args.top))
    return 0


if __name__ == "__main__":
    sys.exit(main())
