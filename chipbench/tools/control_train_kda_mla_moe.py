#!/usr/bin/env python3
"""The readings a ``train_kda_mla_moe`` cell's limits are set from, in one
process on the chip, as ``control_train_hybrid.py`` reads them for
``train_hybrid`` cells: for each seed the program's first steps against the
plain reference that took the program's choice of experts (the sound
runs), with the share of (token, choice) pairs the reference's routers
would have settled otherwise and the first batch's load on the held
experts; and for the first ``--controls`` seeds the reference in the next
lower precision, choosing for itself, against the float32 reference that
took ITS choice (the control), through the cell's own comparison and
limits.  Exits 1 if a control comes out correct.  The bfloat16-rounded
reference the other cells' tools read beside the control is not read
here: its program does not fit beside the job's on this cell's chip (15.84
of 15.75 GB, my chip run, PR 43), and no limit rests on it.

    python3 chipbench/tools/control_train_kda_mla_moe.py --workload ling3flash-train-1chip --seeds 6 --controls 2
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--controls", type=int, default=2)
    ap.add_argument("--first-seed", type=int, default=3_900_000_029)
    ap.add_argument("--seed-list", type=int, nargs="*", default=None,
                    help="these seeds (a run's own, to reproduce it) "
                         "in place of --seeds from --first-seed")
    args = ap.parse_args()

    import jax

    from chainermn_tpu.utils.profiling import setup_compilation_cache
    from chipbench import harness
    from chipbench.runners import train, train_kda_mla_moe
    from chipbench.tools.control_train import gaps
    from chipbench.tools.control_train_hybrid import widest_leaves

    setup_compilation_cache()
    manifest = harness.load_manifest()
    cell, config, mix, limits = harness.find_cell(manifest, args.workload)
    devices = list(jax.devices()[:cell["chips"]])
    if devices[0].platform != "tpu" or len(devices) != cell["chips"]:
        raise SystemExit(f"needs {cell['chips']} TPU chip(s), found "
                         f"{len(devices)} x {devices[0].platform}")
    control = config["precision"]["control"]
    job = train_kda_mla_moe.KdaMlaMoeJob(config, mix, devices)
    job_like = {"replicated": job.replicated, "rows": job.rows}
    rows = []
    seeds = args.seed_list or [
        args.first_seed + 7919 * i for i in range(args.seeds)]
    for i, seed in enumerate(seeds):
        run = harness.Run(manifest, cell, config, mix, limits, seed, 0.0,
                          False, time.perf_counter(), devices)
        t0 = time.perf_counter()
        job.reset(seed)
        readings = train_kda_mla_moe.first_steps(run, job)
        job.release()
        job.routed = []
        load = train_kda_mla_moe.routing_load(config, readings["chosen"][0])
        t1 = time.perf_counter()
        ref = train_kda_mla_moe.reference_readings(
            run, job_like, forced=readings["chosen"])
        t2 = time.perf_counter()

        def all_gaps(low, ref):
            return dict(gaps(low, ref, train.worst_leaf_gap),
                        router=train_kda_mla_moe.differing_pairs_share(
                            low["chosen"], ref["chosen"]))

        row = {"seed": seed, "program_s": t1 - t0, "reference_s": t2 - t1,
               "losses": readings["losses"], "ref_losses": ref["losses"],
               "program": all_gaps(readings, ref),
               "held_pairs": [s["held_pairs"] for s in load.values()],
               "max_load_over_mean": [
                   s["max_load_over_mean"] for s in load.values()],
               "widest_leaves": {
                   key: widest_leaves(readings[key], ref[key])
                   for key in ("grad_norms", "delta_norms")},
               "memory_peak_bytes": harness.device_report(
                   devices)["memory_peak_bytes"]}
        if i < args.controls:
            low, ref_low = train_kda_mla_moe.control_readings(
                run, job_like, control)
            row[control] = all_gaps(low, ref_low)
            train_kda_mla_moe.compare(run, low, ref_low)
            row["control_failed_by"] = [
                c[0] for c in run.checks if not c[3]]
        print(json.dumps(row), flush=True)
        rows.append(row)
    summary = {"workload": args.workload, "seeds": seeds}
    for key in ("loss", "grad", "delta", "router"):
        summary[key] = {
            "program_max": max(r["program"][key] for r in rows),
            "control_min": min((r[control][key] for r in rows
                               if control in r), default=None),
        }
    print("SUMMARY " + json.dumps(summary), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"control_{args.workload}.json"), "w") as f:
        json.dump({"rows": rows, "summary": summary}, f, indent=1)
    passed = [r["seed"] for r in rows if r.get("control_failed_by") == []]
    if passed:
        raise SystemExit(f"the {control} control came out correct on "
                         f"seeds {passed}")


if __name__ == "__main__":
    main()
