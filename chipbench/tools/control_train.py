#!/usr/bin/env python3
"""The readings a training cell's limits are set from, in one process on
the chip: for each seed the program's first steps against the plain
reference (the sound runs), and for the first ``--controls`` seeds the
reference in the next lower precision against itself (the control, which
has to come out as not correct).

    python3 chipbench/tools/control_train.py --workload cgpt-train-1chip --seeds 12 --controls 3
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def gaps(readings, ref, worst_leaf_gap):
    return {
        "loss": max(abs(a - b) / abs(b) for a, b in zip(
            readings["losses"], ref["losses"])),
        "grad": worst_leaf_gap(
            readings["grad_norms"], ref["grad_norms"])[0],
        "delta": worst_leaf_gap(
            readings["delta_norms"], ref["delta_norms"])[0],
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_200_000_011)
    args = ap.parse_args()

    import jax

    from chainermn_tpu.utils.profiling import setup_compilation_cache
    from chipbench import harness
    from chipbench.runners import train

    setup_compilation_cache()
    manifest = harness.load_manifest()
    cell, config, mix, limits = harness.find_cell(manifest, args.workload)
    devices = list(jax.devices()[:cell["chips"]])
    if devices[0].platform != "tpu" or len(devices) != cell["chips"]:
        raise SystemExit(f"needs {cell['chips']} TPU chip(s), found "
                         f"{len(devices)} x {devices[0].platform}")
    control = config["precision"]["control"]
    job = train.TrainJob(config, mix, devices)
    job_like = {"replicated": job.replicated, "rows": job.rows}
    rows = []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        run = harness.Run(manifest, cell, config, mix, limits, seed, 0.0,
                          False, time.perf_counter(), devices)
        t0 = time.perf_counter()
        job.reset(seed)
        readings = train.first_steps(job, int(mix["reference_steps"]))
        job.release()
        t1 = time.perf_counter()
        ref = train.reference_readings(run, job_like)
        t2 = time.perf_counter()
        row = {"seed": seed, "program_s": t1 - t0, "reference_s": t2 - t1,
               "losses": readings["losses"], "ref_losses": ref["losses"],
               "program": gaps(readings, ref, train.worst_leaf_gap)}
        if i < args.controls:
            for prec in (control, "bfloat16"):
                low = train.reference_readings(run, job_like, prec)
                row[prec] = gaps(low, ref, train.worst_leaf_gap)
        print(json.dumps(row), flush=True)
        rows.append(row)
    summary = {"workload": args.workload, "seeds": args.seeds}
    for key in ("loss", "grad", "delta"):
        summary[key] = {
            "program_max": max(r["program"][key] for r in rows),
            "control_min": min(r[control][key] for r in rows
                               if control in r),
            "bfloat16_ref_max": max(r["bfloat16"][key] for r in rows
                                    if "bfloat16" in r),
        }
    print("SUMMARY " + json.dumps(summary), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"control_{args.workload}.json"), "w") as f:
        json.dump({"rows": rows, "summary": summary}, f, indent=1)


if __name__ == "__main__":
    main()
