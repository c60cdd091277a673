#!/usr/bin/env python3
"""Record the capture ``chipbench/tests/test_sdar_moe_files.py`` reads the
``sdar.*`` readers on: ``record_trace.record`` handed one more kind at
run time (``tiny_bd_moe``: the ``train_bd_moe`` step at the flash
kernels' least shapes on the chip — heads of 128, two tiles a copy so
that the mask's walk has interior, cut and diagonal tiles).  Not an entry
of ``record_trace.KINDS``: that table is the benchmark's, and
``captures.py`` rebuilds the accepted cells' contexts from it.

    chiprun -- python3 chipbench/tools/record_bd_moe_trace.py chiprun_out/captures
    cp chiprun_out/captures/tiny_bd_moe.* chipbench/data/
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

NAME = "tiny_bd_moe"
KIND = (
    "sdar30b-train-1chip", "tiny_bd_moe",
    dict(hidden_size=256, head_dim=128, num_attention_heads=4,
         num_key_value_heads=2, moe_intermediate_size=128, vocab_size=512,
         program_flash_block=256),
    dict(global_batch=1, seq_len=512))


def kinds(record_trace):
    """``record_trace.KINDS`` and this one (the tiny module's program
    with the flash blocks pinned to 256, so that 512 tokens a copy are
    two tiles)."""
    cell, module, config, mix = KIND
    config = dict(config)
    block = config.pop("program_flash_block")
    from chipbench.tests import tiny_bd_moe

    config["program"] = dict(tiny_bd_moe.CONFIG["program"],
                             flash_block_q=block, flash_block_k=block)
    return dict(record_trace.KINDS, **{NAME: (cell, module, config, mix)})


def main():
    from chipbench.tools import record_trace

    record_trace.KINDS = kinds(record_trace)
    out_dir = os.path.abspath(sys.argv[1])
    os.makedirs(out_dir, exist_ok=True)
    record_trace.record(NAME, out_dir)


if __name__ == "__main__":
    main()
