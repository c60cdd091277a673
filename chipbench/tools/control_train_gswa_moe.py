#!/usr/bin/env python3
"""The readings a ``train_gswa_moe`` cell's limits are set from, in one
process on the chip, as ``control_train_bd_moe.py`` reads them for
``train_bd_moe`` cells: for each seed the program's first steps against
the plain reference that took the program's choice of experts (the sound
runs), with the share of (token, choice) pairs the reference's routers
would have settled otherwise, the compared attention rows' worst row gap
and the first batch's load on the held experts; for the first
``--controls`` seeds the reference in the next lower precision, choosing
for itself, against the float32 reference that took ITS choice (the
control; for the first ``--bfloat16`` seeds the bfloat16-rounded
reference too, which is to read what a sound run reads); and for the
first ``--broken`` seeds what the TIMED step's compared attention rows
added to the stream in the first step against the reference's with ONE
statement of a row wrong (``refs/laguna.BROKEN``: a sliding row without
its window, the gate left out, the full row turning its whole head, its
attention factor dropped; a forward pass each), held to the cell's own
``attention_row_gap``.  Exits 1 if a sound run comes out not correct, or
a control or a broken row correct, under the cell's committed limits.

    python3 chipbench/tools/control_train_gswa_moe.py --workload laguna-train-1chip --seeds 3 --controls 2 --broken 2
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
    ap.add_argument("--broken", type=int, default=1)
    ap.add_argument("--bfloat16", type=int, default=0)
    ap.add_argument("--first-seed", type=int, default=5_100_000_011)
    ap.add_argument("--seed-list", type=int, nargs="*", default=None,
                    help="these seeds (a run's own, to reproduce it) "
                         "in place of --seeds from --first-seed")
    ap.add_argument("--deadline", type=float, default=None,
                    help="seconds after which no further seed is begun")
    args = ap.parse_args()

    import jax

    from chainermn_tpu.utils.profiling import setup_compilation_cache
    from chipbench import harness
    from chipbench.refs.laguna import BROKEN
    from chipbench.runners import train
    from chipbench.runners import train_gswa_moe as runner
    from chipbench.tools.control_train import gaps
    from chipbench.tools.control_train_hybrid import widest_leaves

    t_start = time.perf_counter()
    setup_compilation_cache()
    manifest = harness.load_manifest()
    cell, config, mix, limits = harness.find_cell(manifest, args.workload)
    devices = list(jax.devices()[:cell["chips"]])
    if devices[0].platform != "tpu" or len(devices) != cell["chips"]:
        raise SystemExit(f"needs {cell['chips']} TPU chip(s), found "
                         f"{len(devices)} x {devices[0].platform}")
    control = config["precision"]["control"]
    job = runner.GswaMoeJob(config, mix, devices)
    rows = []
    seeds = args.seed_list or [
        args.first_seed + 7919 * i for i in range(args.seeds)]
    for i, seed in enumerate(seeds):
        if args.deadline and time.perf_counter() - t_start > args.deadline:
            print(f"deadline: seeds from {seed} on not begun", flush=True)
            break
        run = harness.Run(manifest, cell, config, mix, limits, seed, 0.0,
                          False, time.perf_counter(), devices)
        t0 = time.perf_counter()
        job.reset(seed)
        readings = runner.first_steps(run, job)
        job_like = runner.like(job)
        job.release()
        job.routed = []
        load = runner.routing_load(config, readings["chosen"][0])
        t1 = time.perf_counter()
        ref = runner.reference_readings(
            run, job_like, forced=readings["chosen"])
        t2 = time.perf_counter()

        def all_gaps(low, ref):
            rows = runner.row_gaps(run, low["attention"], ref["attention"])
            return dict(gaps(low, ref, train.worst_leaf_gap),
                        router=runner.differing_pairs_share(
                            low["chosen"], ref["chosen"]),
                        attention=max(rows.values()), attention_rows=rows)

        def held_to(low, ref):
            """The checks ``low`` fails against ``ref`` under the cell's
            own comparison and limits."""
            fresh = harness.Run(manifest, cell, config, mix, limits, seed,
                                0.0, False, t0, devices)
            runner.compare_all(fresh, low, ref)
            return [c[0] for c in fresh.checks if not c[3]]

        row = {"seed": seed, "program_s": t1 - t0, "reference_s": t2 - t1,
               "losses": readings["losses"], "ref_losses": ref["losses"],
               "program": all_gaps(readings, ref),
               "sound_failed_by": held_to(readings, ref),
               "held_pairs": [s["held_pairs"] for s in load.values()],
               "max_load_over_mean": [
                   s["max_load_over_mean"] for s in load.values()],
               "widest_leaves": {
                   key: widest_leaves(readings[key], ref[key])
                   for key in ("grad_norms", "delta_norms")},
               "memory_peak_bytes": harness.device_report(
                   devices)["memory_peak_bytes"]}
        for prec in [control] * (i < args.controls) + (
                ["bfloat16"] * (i < args.bfloat16)):
            low, ref_low = runner.control_readings(run, job_like, prec)
            row[prec] = all_gaps(low, ref_low)
            if prec == control:
                row["control_failed_by"] = held_to(low, ref_low)
        if i < args.broken:
            row["broken"] = {}
            first = [readings["attention"][0]]
            for what in BROKEN:
                wrong = runner.first_attention(
                    run, job_like, readings["chosen"][0], broken=what)
                found = runner.row_gaps(run, first, [wrong])
                row["broken"][what] = dict(found, failed=bool(
                    max(found.values()) > limits["attention_row_gap"]))
        print(json.dumps(row), flush=True)
        rows.append(row)
    summary = {"workload": args.workload, "seeds": [r["seed"] for r in rows]}
    for key in ("loss", "grad", "delta", "router", "attention"):
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
    unsound = [r["seed"] for r in rows if r["sound_failed_by"]]
    passed = [r["seed"] for r in rows if r.get("control_failed_by") == []]
    sound = [(r["seed"], what) for r in rows
             for what, b in r.get("broken", {}).items() if not b["failed"]]
    print(f"VERDICT sound runs not correct: {unsound}; the {control} "
          f"control correct on: {passed}; broken rows correct: {sound}",
          flush=True)
    if unsound or passed or sound:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
