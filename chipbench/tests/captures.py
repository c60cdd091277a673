"""The captures recorded on the chip with their step's text
(``chipbench/data/<name>.xplane.pb.gz`` + ``.hlo.txt.gz``, written by
``chipbench/tools/record_trace.py``), as the readers' context, by the
cell each stands for.  ``cgpt-train-dp4`` has none: a tiny step's buckets
are under the ring's 16 MiB, so its readers are held to a made-up ring
(``test_scope_reduce.py``) and to PERF.md section 6's table, from the
chip."""

import gzip
import importlib.util
import os

import pytest

from chipbench import harness, trace_reduce

DATA = os.path.join(harness.HERE, "data")


def _tool():
    spec = importlib.util.spec_from_file_location(
        "record_trace", os.path.join(harness.HERE, "tools",
                                     "record_trace.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


TOOL = _tool()
#: cell -> the name of the files of the capture that stands for it
RECORDED = {cell: name for name, (cell, *_) in TOOL.KINDS.items()
            if os.path.exists(os.path.join(DATA, name + ".hlo.txt.gz"))}


def recorded(cell):
    """The readers' context on the capture that stands for ``cell``."""
    device_trace = pytest.importorskip(
        "chainermn_tpu.observability.device_trace")
    name = RECORDED[cell]
    _, config, mix, _ = TOOL.context(name)
    trace = trace_reduce.TraceData.from_file(
        os.path.join(DATA, name + ".xplane.pb.gz"), n_devices=1)
    with gzip.open(os.path.join(DATA, name + ".hlo.txt.gz"), "rt") as f:
        table = device_trace.scope_table(f.read())
    return {"trace": trace, "trace_steps": TOOL.STEPS, "scope_table": table,
            "config": config, "mix": mix, "devices": [None],
            "device_kind": "TPU v5 lite", "moe_held_pairs": None}
