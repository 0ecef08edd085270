"""Look at one trace by hand: planes, lines, and the names that took most
time on each line, with the stats of one event of each name.

    python benchmarks/trace/dump.py <trace dir or .xplane.pb> [top]
"""
import os
import sys

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmarks.trace.reduce import find_xplane  # noqa: E402


def dump(path, top=25, out=sys.stdout):
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    print(f"{path}: {os.path.getsize(path)} bytes", file=out)
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines", file=out)
        for line in lines:
            by_name = {}
            n = 0
            for e in line.events:
                n += 1
                rec = by_name.setdefault(e.name, [0.0, 0, None])
                rec[0] += e.duration_ns
                rec[1] += 1
                if rec[2] is None:
                    rec[2] = [(k, str(v)[:160]) for k, v in e.stats]
            print(f"  LINE {line.name!r}: {n} events, {len(by_name)} names",
                  file=out)
            ranked = sorted(by_name.items(), key=lambda kv: -kv[1][0])
            for name, (ns, calls, stats) in ranked[:top]:
                print(f"    {ns / 1e6:12.3f} ms {calls:7d}x  {name}",
                      file=out)
                print(f"        {stats}", file=out)


if __name__ == "__main__":
    dump(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 25)
