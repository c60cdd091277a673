#!/usr/bin/env python3
"""Record the capture ``chipbench/tests/test_laguna_files.py`` reads the
``laguna.*`` readers on: ``record_trace.record`` handed one more kind at
run time (``tiny_gswa_moe``: the ``train_gswa_moe`` step at the flash
kernels' least shapes on the chip — heads of 128, query heads a layer of
4 and 6 over 2, a window of 256 at 1,024 tokens so that the band has a
diagonal and a far tile a query block).  Not an entry of
``record_trace.KINDS``: that table is the benchmark's, and
``captures.py`` rebuilds the accepted cells' contexts from it.

    chiprun -- python3 chipbench/tools/record_gswa_moe_trace.py chiprun_out/captures
    cp chiprun_out/captures/tiny_gswa_moe.* chipbench/data/
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

NAME = "tiny_gswa_moe"
KIND = (
    "laguna-train-1chip", "tiny_gswa_moe",
    dict(hidden_size=256, head_dim=128, intermediate_size=512,
         moe_intermediate_size=128, shared_expert_intermediate_size=128,
         sliding_window=256, vocab_size=512),
    dict(global_batch=1, seq_len=1024))


def kinds(record_trace):
    """``record_trace.KINDS`` and this one."""
    return dict(record_trace.KINDS, **{NAME: KIND})


def main():
    from chipbench.tools import record_trace

    record_trace.KINDS = kinds(record_trace)
    out_dir = os.path.abspath(sys.argv[1])
    os.makedirs(out_dir, exist_ok=True)
    record_trace.record(NAME, out_dir)


if __name__ == "__main__":
    main()
