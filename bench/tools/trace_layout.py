"""Print the layout of a profiler trace: each plane, its lines, their event
counts and the event names that take most time on each line.

    python3 bench/tools/trace_layout.py <trace dir or .xplane.pb> [top]
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

from benchkit.trace import xplane_file  # noqa: E402


def main(path: str, top: int = 8) -> None:
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = xplane_file(path)
    print(f"{path}: {os.path.getsize(path)} bytes")
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            by: dict = {}
            n = 0
            first = last = None
            for e in line.events:
                n += 1
                c, t = by.get(e.name, (0, 0.0))
                by[e.name] = (c + 1, t + e.duration_ns)
                first = e.start_ns if first is None else min(first, e.start_ns)
                last = e.start_ns + e.duration_ns if last is None else max(last, e.start_ns + e.duration_ns)
            print(f"  line {line.name!r}: {n} events, {first} .. {last} ns")
            for name, (c, t) in sorted(by.items(), key=lambda kv: -kv[1][1])[:top]:
                print(f"    {t / 1e6:12.3f} ms  x{c:<6d} {name[:120]}")


if __name__ == "__main__":
    main(sys.argv[1], *(int(x) for x in sys.argv[2:3]))
