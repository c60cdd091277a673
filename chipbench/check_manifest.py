#!/usr/bin/env python3
"""Check ``BENCHMARK.json`` against what the driver refuses before a run.

    python3 chipbench/check_manifest.py [path/to/BENCHMARK.json]

Runs on the CPU, touches no JAX device.  PR 22 was refused for one of
these (a pair of configuration and traffic given twice) before any chip
time was spent; this makes that refusal, and the others of its kind,
visible here first.  Prints every fault; exit 0 only when there is none."""

import json
import os
import re
import sys

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "proj",
               "n_embd", "n_inner", "d_model", "d_ff", "head_dim",
               "expansion", "experts_per_tok")


def one_line(text, what, faults):
    if not isinstance(text, str) or not 1 <= len(text) <= 200 \
            or "\n" in text or "\t" in text:
        faults.append(f"{what}: 1 to 200 characters on one line, no tab")


def keys(entry, required, optional, what, faults):
    extra = set(entry) - set(required) - set(optional)
    missing = set(required) - set(entry)
    if extra:
        faults.append(f"{what}: keys not allowed: {sorted(extra)}")
    if missing:
        faults.append(f"{what}: keys missing: {sorted(missing)}")


def check(manifest, root, size=None):
    faults = []
    if size is not None and size > 64 * 1024:
        faults.append(f"the manifest is {size} bytes, over 64 KiB")
    if set(manifest) != TOP_KEYS:
        faults.append(f"top-level keys must be exactly {sorted(TOP_KEYS)}, "
                      f"got {sorted(manifest)}")
        return faults
    paths = manifest["paths"]
    if not 1 <= len(paths) <= 16:
        faults.append("paths: 1 to 16 directories")
    for p in paths:
        if not PATH.match(p) or p.startswith("/") or ".." in p.split("/"):
            faults.append(f"paths: {p!r} is not a relative path of the "
                          f"allowed characters")
        elif not os.path.isdir(os.path.join(root, p)):
            faults.append(f"paths: {p!r} is not a directory")
    command = manifest["command"]
    if not 1 <= len(command) <= 32:
        faults.append("command: 1 to 32 strings")
    for word in command:
        one_line(word, f"command word {word!r}", faults)
        if word.startswith("/") or ".." in word.split("/"):
            faults.append(f"command: {word!r} is absolute or leads out")
        elif os.path.exists(os.path.join(root, word)) and not any(
                word == p or word.startswith(p + "/") for p in paths):
            faults.append(f"command: {word!r} is a file outside paths")
    rs = manifest["run_seconds"]
    if not isinstance(rs, int) or not 1 <= rs <= 51:
        faults.append("run_seconds: a whole number from 1 to 51")
    elif 338 * (rs + 60) + 24 * 180 + 1200 > 43200:
        faults.append("run_seconds: a full check of 24 cells would not fit")

    def names(entries, what):
        seen = set()
        for e in entries:
            n = e.get("name", "")
            if not isinstance(n, str) or not NAME.match(n):
                faults.append(f"{what} name {n!r}: at most 64 letters, "
                              f"digits, _ . -, starting with none of . -")
            if n in seen:
                faults.append(f"{what} name {n!r} is given twice")
            seen.add(n)
        return seen

    configs = manifest["configs"]
    if not 1 <= len(configs) <= 24:
        faults.append("configs: 1 to 24")
    config_names = names(configs, "config")
    files = set()
    for c in configs:
        what = f"config {c.get('name')!r}"
        keys(c, ("name", "source", "file", "reduced", "why"), (), what,
             faults)
        one_line(c.get("source"), what + " source", faults)
        one_line(c.get("why"), what + " why", faults)
        f = c.get("file", "")
        if not any(f.startswith(p + "/") for p in paths):
            faults.append(f"{what}: file {f!r} is not under paths")
        elif not os.path.isfile(os.path.join(root, f)):
            faults.append(f"{what}: file {f!r} does not exist")
        if f in files:
            faults.append(f"{what}: file {f!r} is another config's too")
        files.add(f)
        reduced = c.get("reduced", [])
        if len(reduced) > 16:
            faults.append(f"{what}: reduced has more than 16 keys")
        for k in reduced:
            if not NAME.match(k):
                faults.append(f"{what}: reduced key {k!r} is not a name")
            if k.endswith(("_dim", "_rank")) or any(
                    w in k for w in WIDTH_WORDS):
                faults.append(f"{what}: reduced names a width: {k!r}")

    cells = manifest["workloads"]
    if not 1 <= len(cells) <= 24:
        faults.append("workloads: 1 to 24")
    cell_names = names(cells, "workload")
    pairs, used = set(), set()
    for w in cells:
        what = f"workload {w.get('name')!r}"
        keys(w, ("name", "config", "traffic", "chips", "why"), (), what,
             faults)
        one_line(w.get("why"), what + " why", faults)
        if w.get("config") not in config_names:
            faults.append(f"{what}: unknown config {w.get('config')!r}")
        if not NAME.match(str(w.get("traffic", ""))):
            faults.append(f"{what}: traffic {w.get('traffic')!r} is not a "
                          f"name")
        if w.get("chips") not in (1, 4):
            faults.append(f"{what}: chips must be 1 or 4")
        pair = (w.get("config"), w.get("traffic"))
        if pair in pairs:
            faults.append(
                f"the pair of config and traffic {pair[0]} with {pair[1]} "
                f"is given twice; every pair may be given once")
        pairs.add(pair)
        used.add(w.get("config"))
        for sub, name in (("traffic", w.get("traffic")),
                          ("limits", w.get("name"))):
            f = os.path.join(paths[0], sub, f"{name}.json")
            if not os.path.isfile(os.path.join(root, f)):
                faults.append(f"{what}: {f} does not exist")
    for c in sorted(config_names - used):
        faults.append(f"config {c!r} is used by no cell")
    four = sum(1 for w in cells if w.get("chips") == 4)
    if four > max(1, len(cells) // 4):
        faults.append(f"{four} of {len(cells)} cells ask for 4 chips; at "
                      f"most 25%, rounded down, may (one always may)")

    e2e = manifest["end_to_end"]
    layers = manifest["per_layer"]
    if not 1 <= len(e2e) <= 16:
        faults.append("end_to_end: 1 to 16")
    if not 1 <= len(layers) <= 128:
        faults.append("per_layer: 1 to 128")
    names(e2e + layers, "metric")
    reports = {}                         # e2e metric -> cells reporting it
    for m in e2e:
        what = f"metric {m.get('name')!r}"
        keys(m, ("name", "unit", "better", "bound", "source"),
             ("workloads",), what, faults)
        if m.get("source") not in ("host_clock", "device_trace"):
            faults.append(f"{what}: an end-to-end metric takes host_clock "
                          f"or device_trace")
        b = m.get("bound")
        if not isinstance(b, (int, float)) or not 0.01 <= b <= 0.1:
            faults.append(f"{what}: bound from 0.01 to 0.1")
        reports[m.get("name")] = set(m.get("workloads", cell_names))
    if "setup_s" not in reports:
        faults.append("end_to_end has no setup_s")
    elif reports["setup_s"] != cell_names:
        faults.append("setup_s must be reported by every cell")
    for m in e2e + layers:
        what = f"metric {m.get('name')!r}"
        if not UNIT.match(str(m.get("unit", ""))):
            faults.append(f"{what}: unit {m.get('unit')!r}")
        if m.get("better") not in ("lower", "higher"):
            faults.append(f"{what}: better is lower or higher")
        if m.get("source") not in SOURCES:
            faults.append(f"{what}: source {m.get('source')!r}")
        for c in m.get("workloads", ()):
            if c not in cell_names:
                faults.append(f"{what}: unknown workload {c!r}")
    covered = {c: 0 for c in cell_names}
    for m in layers:
        what = f"metric {m.get('name')!r}"
        keys(m, ("name", "unit", "better", "source", "layer", "moves"),
             ("workloads",), what, faults)
        one_line(m.get("layer"), what + " layer", faults)
        moved = m.get("moves")
        if moved not in reports:
            faults.append(f"{what}: moves {moved!r}, not an end-to-end "
                          f"metric")
            continue
        for c in m.get("workloads", reports[moved]):
            if c not in reports[moved]:
                faults.append(f"{what}: cell {c!r} does not report "
                              f"{moved!r}")
            covered[c] = covered.get(c, 0) + 1
        reader = os.path.join(root, paths[0], "layer_metrics", m["name"])
        if not (os.path.isfile(reader + ".json")
                or os.path.isfile(reader + ".py")):
            faults.append(f"{what}: no reader {reader}.json or .py")
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            if m.get("unit") != "%":
                faults.append(f"{what}: a roofline or mfu share has unit %")
    for c in sorted(cell_names):
        others = [n for n, cs in reports.items()
                  if n != "setup_s" and c in cs]
        if not others:
            faults.append(f"workload {c!r} reports no end-to-end metric "
                          f"besides setup_s")
        if not covered.get(c):
            faults.append(f"workload {c!r} reports no per-layer metric")
    return faults


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = argv[0] if argv else os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        text = f.read()
    faults = check(json.loads(text), os.path.dirname(os.path.abspath(path)),
                   len(text.encode()))
    for fault in faults:
        print("FAULT:", fault)
    print(f"{path}: {'ok' if not faults else f'{len(faults)} fault(s)'}")
    return 1 if faults else 0


if __name__ == "__main__":
    sys.exit(main())
