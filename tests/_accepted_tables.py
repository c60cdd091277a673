"""The block tables of the benchmark's accepted configurations, as the
cells' runners build them, each as the SHA-256 of its ``repr``: the
frozen dataclasses print every field, so two tables with one digest are
``==``.  ``tests/golden/accepted_tables.json`` holds the digests of the
commit BEFORE a change to ``models/block_table.py``; regenerate it in a
checkout of that commit:

    PYTHONPATH=<that checkout> python tests/_accepted_tables.py \\
        tests/golden/accepted_tables.json
"""

import hashlib
import importlib
import json
import os
import sys

#: configuration -> the runner whose ``build_table`` the cell runs
#: (granite's builds its table inline, from the file's keys alone).
RUNNERS = {
    "granite4hmicro-train": None,
    "nemotron3nano-train": "train_moe_hybrid",
    "zaya1-8b-train": "train_cca_moe",
    "qwen3next-80b-a3b-train": "train_gdn_moe",
    "mellum2-12b-a2.5b-train": "train_gdn_moe",
    "ling3flash-train": "train_kda_mla_moe",
    "sdar-30b-a3b-train": "train_bd_moe",
}


def tables(root):
    from chainermn_tpu.models.block_table import table_from_config

    out = {}
    for name, runner in RUNNERS.items():
        with open(os.path.join(
                root, "chipbench", "configs", name + ".json")) as f:
            config = json.load(f)
        if runner is None:
            out[name] = table_from_config(config,
                                          n_layers=config["n_layer"])
        else:
            out[name] = importlib.import_module(
                "chipbench.runners." + runner).build_table(config)
    return out


def digests(root):
    return {name: {"layers": len(t.layers), "sha256": hashlib.sha256(
        repr(t).encode()).hexdigest()} for name, t in tables(root).items()}


if __name__ == "__main__":
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(sys.argv[1], "w") as f:
        json.dump(digests(root), f, indent=1, sort_keys=True)
        f.write("\n")
