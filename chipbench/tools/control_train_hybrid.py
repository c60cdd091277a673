#!/usr/bin/env python3
"""The readings a ``train_hybrid`` cell's limits are set from, in one
process on the chip, as ``control_train.py`` reads them for ``train``
cells: for each seed the program's first steps against the plain reference
(the sound runs), and for the first ``--controls`` seeds the reference in
the next lower precision against itself (the control, which has to come
out as not correct).

    python3 chipbench/tools/control_train_hybrid.py --workload granite4hm-train-1chip --seeds 6 --controls 2
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def widest_leaves(program, ref, n=4):
    """The ``n`` leaves whose norms differ most, as ``worst_leaf_gap``
    measures a leaf: ``[gap, name, program's norm, reference's]``."""
    import statistics

    from chipbench import weights

    p, r = weights.flatten(program), weights.flatten(ref)
    floor = statistics.median(float(x) for x in r.values())
    rows = [[abs(float(p[k]) - float(r[k])) / max(float(r[k]), floor),
             weights.leaf_name(k), float(p[k]), float(r[k])] for k in r]
    return sorted(rows, reverse=True)[:n] + [["floor", floor]]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--controls", type=int, default=2)
    ap.add_argument("--first-seed", type=int, default=2_600_000_011)
    ap.add_argument("--seed-list", type=int, nargs="*", default=None,
                    help="these seeds (a run's own, to reproduce it) "
                         "in place of --seeds from --first-seed")
    args = ap.parse_args()

    import jax

    from chainermn_tpu.utils.profiling import setup_compilation_cache
    from chipbench import harness
    from chipbench.runners import train, train_hybrid
    from chipbench.tools.control_train import gaps

    setup_compilation_cache()
    manifest = harness.load_manifest()
    cell, config, mix, limits = harness.find_cell(manifest, args.workload)
    devices = list(jax.devices()[:cell["chips"]])
    if devices[0].platform != "tpu" or len(devices) != cell["chips"]:
        raise SystemExit(f"needs {cell['chips']} TPU chip(s), found "
                         f"{len(devices)} x {devices[0].platform}")
    control = config["precision"]["control"]
    job = train_hybrid.HybridJob(config, mix, devices)
    job_like = {"replicated": job.replicated, "rows": job.rows}
    rows = []
    seeds = args.seed_list or [
        args.first_seed + 7919 * i for i in range(args.seeds)]
    for i, seed in enumerate(seeds):
        run = harness.Run(manifest, cell, config, mix, limits, seed, 0.0,
                          False, time.perf_counter(), devices)
        t0 = time.perf_counter()
        job.reset(seed)
        readings = train.first_steps(job, int(mix["reference_steps"]))
        job.release()
        t1 = time.perf_counter()
        ref = train_hybrid.reference_readings(run, job_like)
        t2 = time.perf_counter()
        row = {"seed": seed, "program_s": t1 - t0, "reference_s": t2 - t1,
               "losses": readings["losses"], "ref_losses": ref["losses"],
               "program": gaps(readings, ref, train.worst_leaf_gap),
               "widest_leaves": {
                   key: widest_leaves(readings[key], ref[key])
                   for key in ("grad_norms", "delta_norms")},
               "memory_peak_bytes": harness.device_report(
                   devices)["memory_peak_bytes"]}
        if i < args.controls:
            for prec in (control, "bfloat16"):
                low = train_hybrid.reference_readings(run, job_like, prec)
                row[prec] = gaps(low, ref, train.worst_leaf_gap)
        print(json.dumps(row), flush=True)
        rows.append(row)
    summary = {"workload": args.workload, "seeds": seeds}
    for key in ("loss", "grad", "delta"):
        summary[key] = {
            "program_max": max(r["program"][key] for r in rows),
            "control_min": min((r[control][key] for r in rows
                               if control in r), default=None),
            "bfloat16_ref_max": max((r["bfloat16"][key] for r in rows
                                    if "bfloat16" in r), default=None),
        }
    print("SUMMARY " + json.dumps(summary), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"control_{args.workload}.json"), "w") as f:
        json.dump({"rows": rows, "summary": summary}, f, indent=1)


if __name__ == "__main__":
    main()
