#!/usr/bin/env python3
"""Prints the structure of an `.xplane.pb` (planes, lines, the first
events of each with their stats): the look by hand that comes before
code is written against a trace.

    python3 benchmark/dump_trace.py <file.xplane.pb> [events per line]
"""
import sys


def main(path, n=6):
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", line.name, len(events), "events")
            for ev in events[:n]:
                stats = [(k, str(v)[:60]) for k, v in list(ev.stats)[:6]]
                print(f"    {ev.name[:90]!r} start {ev.start_ns:.0f} "
                      f"dur {ev.duration_ns:.0f} {stats}")


if __name__ == "__main__":
    main(sys.argv[1], *(int(x) for x in sys.argv[2:3]))
