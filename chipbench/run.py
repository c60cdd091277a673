#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json``:

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  It runs on the machine it is started on,
refuses to run without a TPU or with fewer chips than the cell asks for
(exit 2, no result line), and prints one JSON object as the last line of
its standard output."""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness

    manifest = harness.load_manifest()
    cell, config, mix, limits = harness.find_cell(manifest, args.workload)

    import chainermn_tpu  # noqa: F401  (absent: no system to measure)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chipbench: no TPU was found (JAX reports platform "
            f"{devices[0].platform!r}); the benchmark runs on the chip only")
    if len(devices) < cell["chips"]:
        raise SystemExit(
            f"chipbench: cell {cell['name']} needs {cell['chips']} chips, "
            f"JAX sees {len(devices)}")
    from chainermn_tpu.utils.profiling import setup_compilation_cache

    harness.log(f"compile cache: {setup_compilation_cache()}")
    run = harness.Run(
        manifest=manifest, cell=cell, config=config, mix=mix,
        limits=limits, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), t_start=T_START,
        devices=list(devices[:cell["chips"]]))
    line = harness.execute(run)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
