#!/usr/bin/env python3
"""Look at one trace by hand: planes, lines, how many events each holds,
the operations that took most time with one event's stats, and the compiled
programs by name. ``python3 perfbench/tools/trace_peek.py <trace dir>``"""
import collections
import glob
import os
import sys


def main(directory):
    import jax
    path = glob.glob(os.path.join(directory, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", line.name, len(events))
            if not plane.name.startswith("/device:"):
                continue
            total, sample = collections.Counter(), {}
            for e in events:
                total[e.name] += e.duration_ns
                sample.setdefault(e.name, e)
            for name, ns in total.most_common(40):
                e = sample[name]
                print(f"    {ns / 1e6:10.3f} ms  {name}  "
                      f"{dict(list(e.stats)[:8])}")


if __name__ == "__main__":
    main(sys.argv[1])
