"""Look at one trace by hand before trusting the reducer: planes, lines,
event counts, time ranges, and the first events of each line with their
statistics.

    python3 benchmark/tools/trace_dump.py <file.xplane.pb> [events-per-line]
"""

import sys

import jax


def main() -> None:
    path = sys.argv[1]
    n = int(sys.argv[2]) if len(sys.argv) > 2 else 4
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            if not evs:
                continue
            lo = min(e.start_ns for e in evs)
            hi = max(e.start_ns + e.duration_ns for e in evs)
            print(f"  LINE {line.name!r}: {len(evs)} events, "
                  f"{lo:.0f} .. {hi:.0f} ns ({(hi - lo) / 1e6:.1f} ms)")
            for e in evs[:n]:
                try:
                    st = {k: (v if not isinstance(v, (str, bytes))
                              else str(v)[:160]) for k, v in e.stats}
                except Exception as err:
                    st = f"<stats unreadable: {err}>"
                print(f"      {e.name[:80]!r} start {e.start_ns:.0f} dur "
                      f"{e.duration_ns:.0f} {st}")


if __name__ == "__main__":
    main()
