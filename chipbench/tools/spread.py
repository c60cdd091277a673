#!/usr/bin/env python3
"""Spreads of the end-to-end metrics over sets of runs, as the contract
measures them: for each set the distance between the first and the third
quartile (``statistics.quantiles(values, n=4)``) as a share of the median.

    python3 chipbench/tools/spread.py chiprun_out/sets_<cell>.jsonl

Each line of the file is ``{"set": "A", "seed": n, "rc": 0, "line": {...}}``
with ``line`` the run's result object."""

import json
import statistics
import sys


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main():
    rows = [json.loads(x) for x in open(sys.argv[1]) if x.strip()]
    bad = [r for r in rows if r["rc"] != 0 or not r["line"]
           or not r["line"]["correct"] or r["line"]["failed"]]
    print(f"runs {len(rows)}, not correct / failed / crashed: {len(bad)}")
    for r in bad:
        print("  BAD", r["set"], r["seed"], r["rc"],
              (r["line"] or {}).get("correct"), (r["line"] or {}).get("failed"))
    good = [r for r in rows if r["line"]]
    names = sorted({k for r in good for k in r["line"]["metrics"]})
    sets = sorted({r["set"] for r in good})
    for name in names:
        out = []
        for s in sets:
            v = [r["line"]["metrics"][name]["value"] for r in good
                 if r["set"] == s and name in r["line"]["metrics"]]
            if len(v) >= 2:
                out.append((s, len(v), statistics.median(v), spread(v),
                            min(v), max(v)))
        text = "  ".join(
            f"{s}: n={n} median={m:.4f} spread={sp * 100:.3f}% "
            f"[{lo:.4f}..{hi:.4f}]" for s, n, m, sp, lo, hi in out)
        widest = max((o[3] for o in out), default=0.0)
        print(f"{name}: {text}  -> widest {widest * 100:.3f}%, "
              f"x5 = {widest * 500:.2f}%")
    peaks = {r["line"]["device"]["memory_peak_bytes"] for r in good}
    print("memory_peak_bytes:", sorted(peaks))


if __name__ == "__main__":
    main()
