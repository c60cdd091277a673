#!/usr/bin/env python3
"""Run one traced cell and keep its trace, to read one by hand:

    python3 chipbench/tools/trace_probe.py <out_dir> --workload ... (run.py's arguments)

Writes ``<out_dir>/*.xplane.pb`` and ``<out_dir>/describe.txt``."""

import glob
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "chipbench"))


def main():
    out_dir = os.path.abspath(sys.argv[1])
    import run as entry

    from chipbench import harness, trace_reduce

    harness.ProfilerSlice.keep_dir = out_dir
    entry.main(sys.argv[2:])
    for path in glob.glob(os.path.join(out_dir, "*.xplane.pb")):
        with open(os.path.join(out_dir, "describe.txt"), "w") as f:
            f.write(trace_reduce.describe(path))


if __name__ == "__main__":
    main()
