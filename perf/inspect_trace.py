"""Look at one profiler trace by hand: which planes are devices, which
lines they carry, how operations are named and what statistics the trace
attaches to them. `python3 perf/inspect_trace.py <trace dir or .xplane.pb>`"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    from jax.profiler import ProfileData

    from perf import xtrace

    path = sys.argv[1]
    if os.path.isdir(path):
        path = xtrace.find_xplane(path)
    data = ProfileData.from_file(path)
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r} lines={len(lines)}")
        for line in lines:
            events = list(line.events)
            if not events:
                continue
            span = (events[0].start_ns, max(e.start_ns + e.duration_ns for e in events))
            print(f"  LINE {line.name!r} events={len(events)} span_ms={(span[1] - span[0]) / 1e6:.1f}")
            if plane.name.startswith("/host") and not any(e.name.startswith("bench/") for e in events):
                continue
            sums = {}
            sample = {}
            for e in events:
                sums[e.name] = sums.get(e.name, 0.0) + e.duration_ns
                sample.setdefault(e.name, e)
            for name, ns in sorted(sums.items(), key=lambda kv: -kv[1])[:25]:
                stats = {k: (str(v)[:120]) for k, v in dict(sample[name].stats).items()}
                print(f"    {ns / 1e6:10.3f} ms  {name[:90]!r}  {stats}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
