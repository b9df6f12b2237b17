#!/usr/bin/env python3
"""Device seconds by scope of one trace, as a table for PERF.md: the
window between the two ``perfbench_sync`` marks, the first device, each
operation under the innermost ``jax.named_scope`` name the program gave it
(``harness/scopes.py``), with the largest operations of each scope.

    python3 perfbench/tools/scopes.py <trace dir> [--ops 3]
"""
import argparse
import glob
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench.harness import scopes, tracing  # noqa: E402


def main():
    p = argparse.ArgumentParser()
    p.add_argument("directory")
    p.add_argument("--ops", type=int, default=3)
    args = p.parse_args()
    path = glob.glob(os.path.join(args.directory, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    sync = tracing.read_planes(path)["sync"]
    ops = scopes.device_ops(path, sync[0], sync[-1])
    found = scopes.table(ops)
    busy = found["busy_s"]
    print(f"traced {sync[-1] - sync[0]:.3f} s, busy {busy:.3f} s, "
          f"{len(ops)} operations")
    print("| scope | seconds | share of busy | backward | largest operations |")
    print("|---|---|---|---|---|")
    for name, seconds in sorted(found["scopes"].items(),
                                key=lambda kv: -kv[1]):
        mine = [o for o in ops if (o[3][-1] if o[3] else scopes.UNSCOPED)
                == name]
        back = sum(b - a for _, a, b, _, t in mine if t)
        by_label = {}
        for text, a, b, _, _ in mine:
            by_label[tracing.label(text)] = by_label.get(
                tracing.label(text), 0.0) + (b - a)
        top = ", ".join(f"`{k}` {v:.3f}" for k, v in sorted(
            by_label.items(), key=lambda kv: -kv[1])[:args.ops])
        print(f"| `{name}` | {seconds:.4f} | {100 * seconds / busy:.1f} % | "
              f"{back:.4f} | {top} |")


if __name__ == "__main__":
    main()
