#!/usr/bin/env python3
"""One kept trace, two sets of per-layer readers: what a ``benchmark`` PR
that folds, renames or re-points readers shows its work with.

    # on the chip: one traced run of a cell, its trace and what the
    # readers are handed beside it kept in <dir>
    python3 chipbench/tools/compare_readers.py record <dir> --workload <cell> --seed <n> --seconds 20 --trace 1
    # anywhere (no device is touched): every reader a checkout's manifest
    # lists for that cell, on that one trace -> <dir>/<label>.json
    python3 chipbench/tools/compare_readers.py read <dir> <label> [<checkout>]
    # the table: cell | old name | new name | old value | new value
    python3 chipbench/tools/compare_readers.py table <dir> <old label> <new label> [<renames.json>]

``record`` keeps ``<dir>/*.xplane.pb``, the per-layer values of its result line
(``metrics.json``) and ``ctx.pkl``: the readers' context without the trace
(each side parses the file with its own ``trace_reduce``), the devices
(``None`` for each) and what a reader memoised.  ``read`` imports
``chipbench`` from ``<checkout>`` (default: this one), so a parent
unpacked with ``git archive`` reads with its own readers, reducers and
flops modules.  ``renames.json`` maps an old name to its successor;
a name not in it keeps itself.
"""

import glob
import json
import os
import pickle
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
#: What ``record`` leaves out of the pickle: made anew by each side.
NOT_KEPT = ("trace", "devices", "notes", "_scope_reduce")


def keep(out_dir):
    """Have the traced runs of this process leave their trace, their
    result line and the readers' context in ``out_dir``."""
    sys.path.insert(0, ROOT)
    from chipbench import harness

    harness.ProfilerSlice.keep_dir = out_dir
    os.makedirs(out_dir, exist_ok=True)
    read_layer_metrics = harness.read_layer_metrics

    def keeping(manifest, cell_name, ctx, *args):
        metrics = read_layer_metrics(manifest, cell_name, ctx, *args)
        kept = {k: v for k, v in ctx.items() if k not in NOT_KEPT}
        kept.update(cell=cell_name, n_devices=len(ctx["devices"]))
        with open(os.path.join(out_dir, "ctx.pkl"), "wb") as f:
            pickle.dump(kept, f)
        with open(os.path.join(out_dir, "metrics.json"), "w") as f:
            json.dump(metrics, f)
        return metrics

    harness.read_layer_metrics = keeping


def record(out_dir, argv):
    keep(out_dir)
    sys.path.insert(0, os.path.join(ROOT, "chipbench"))
    import run as entry

    entry.main(argv)


def read(out_dir, label, checkout=ROOT):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # chipbench from <checkout>; the program (the scope table's class and
    # the attribution) from this one, where the checkout holds none
    sys.path[:0] = [os.path.abspath(checkout), ROOT]
    from chipbench import harness, trace_reduce

    with open(os.path.join(out_dir, "ctx.pkl"), "rb") as f:
        ctx = pickle.load(f)
    (path,) = glob.glob(os.path.join(out_dir, "*.xplane.pb"))
    ctx["devices"] = [None] * ctx["n_devices"]
    ctx["trace"] = trace_reduce.TraceData.from_file(
        path, n_devices=ctx["n_devices"])
    bench_dir = os.path.join(os.path.abspath(checkout), "chipbench")
    manifest = harness.load_manifest(
        os.path.join(os.path.abspath(checkout), "BENCHMARK.json"))
    values = {}
    for metric in harness.cell_metrics(manifest, ctx["cell"], "per_layer"):
        values[metric["name"]] = harness.layer_reader(
            metric["name"], bench_dir)(ctx)
    out = {"cell": ctx["cell"], "checkout": os.path.abspath(checkout),
           "values": values}
    with open(os.path.join(out_dir, label + ".json"), "w") as f:
        json.dump(out, f, indent=1)
    return out


def table(out_dir, old_label, new_label, renames=None):
    def load(label):
        with open(os.path.join(out_dir, label + ".json")) as f:
            return json.load(f)

    old, new = load(old_label), load(new_label)
    if renames:
        with open(renames) as f:
            renames = json.load(f)
    rows, unequal = [], 0
    for name, value in old["values"].items():
        successor = (renames or {}).get(name, name)
        got = new["values"].get(successor, "absent")
        same = got == value
        unequal += not same
        rows.append([old["cell"], name, successor, value, got,
                     "equal" if same else "DIFFERS"])
    for name in sorted(set(new["values"]) - {r[2] for r in rows}):
        rows.append([new["cell"], "(new in this cell)", name, None,
                     new["values"][name], "new"])
    for row in rows:
        print(" | ".join(str(x) for x in row))
    print(f"{old['cell']}: {len(rows)} rows, {unequal} differ")
    return rows


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    what, out_dir, rest = argv[0], os.path.abspath(argv[1]), argv[2:]
    if what == "record":
        record(out_dir, rest)
    elif what == "read":
        print(json.dumps(read(out_dir, *rest)["values"]))
    elif what == "table":
        table(out_dir, *rest)
    else:
        raise SystemExit(__doc__)


if __name__ == "__main__":
    main()
