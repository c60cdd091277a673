#!/usr/bin/env python3
"""Record the small trace ``chipbench/tests`` check the reduction on: the
tiny training run of ``chipbench/tests/tiny.py``, traced, on the chip.

    python3 chipbench/tools/record_tiny_trace.py <out_dir>
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    from chipbench import harness, trace_reduce
    from chipbench.tests import tiny

    out_dir = os.path.abspath(sys.argv[1])
    harness.ProfilerSlice.keep_dir = out_dir
    per_layer = [
        {"name": n, "unit": "ms", "better": "lower", "source": "device_trace",
         "layer": "x", "moves": "train_step_ms"}
        for n in ("kernel.flash_ms",
                  "device.idle.train")]
    line, _ = tiny.tiny_run(seed=1, seconds=1.0, trace=True,
                            mix={"global_batch": 4, "trace_steps": 3},
                            per_layer=per_layer)
    print(json.dumps(line))
    for name in os.listdir(out_dir):
        if name.endswith(".xplane.pb"):
            with open(os.path.join(out_dir, "describe.txt"), "w") as f:
                f.write(trace_reduce.describe(os.path.join(out_dir, name)))


if __name__ == "__main__":
    main()
