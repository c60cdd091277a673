#!/usr/bin/env python3
"""SHA-256 of every cell's train step as it LOWERS for a described TPU
v5e, off the chip: what a PR that touches shared code compares with its
parent commit's ("the accepted cells' steps lower to the parent's text").

    python benchmarks/lowered_steps.py <tree> <out.json> [cell ...]

``<tree>`` is a checkout (``git archive <commit> | tar -x -C <dir>``).
The digests of two trees compare only where both were lowered FROM THE
SAME PATH: a Mosaic kernel's serialized body carries the source
locations of its ops, file names among them.  Put a symlink at one path
and point it at each tree in turn; call-site tracebacks are left out of
the locations here (``jax_traceback_in_locations_limit`` 0), so that a
line added above a CALL of a kernel moves no digest, and a line added
inside a kernel's own body still does.  A cell's job is built by its own
runner on the described chips with abstract parameters, state and batch
(nothing is placed, nothing runs); ~5 s a cell.
"""

import hashlib
import importlib
import json
import os
import sys


class _Lowered(Exception):
    """Carries a ``jax.stages.Lowered`` out of a job's ``scope_table``."""


def main(argv):
    tree = os.path.abspath(argv[0])
    sys.path.insert(0, tree)
    os.chdir(tree)
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_traceback_in_locations_limit", 0)
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    for name in ("flash_attention", "grouped_matmul", "ssd", "gated_delta",
                 "kda"):
        module = importlib.import_module("chainermn_tpu.ops." + name)
        if hasattr(module, "default_interpret"):
            module.default_interpret = lambda: False
    from chipbench import harness, weights

    def stop_at_compile(self, *args, **kwargs):
        raise _Lowered(self)

    jax.stages.Lowered.compile = stop_at_compile
    manifest = harness.load_manifest()
    out = {}
    for name in argv[2:] or [w["name"] for w in manifest["workloads"]]:
        cell, config, mix, _ = harness.find_cell(manifest, name)
        runner = importlib.import_module("chipbench.runners." + mix["kind"])
        (job_class,) = [
            v for k, v in vars(runner).items() if k.endswith("Job")
            and isinstance(v, type) and v.__module__ == runner.__name__]
        job = job_class(config, mix, list(topo.devices[:cell["chips"]]))
        if mix["kind"] == "train_bd_moe":
            from chipbench import traffic_bd

            host = traffic_bd.train_batches(mix, config, 7)(0)
        else:
            from chipbench import traffic

            host = traffic.train_batches(mix, config["vocab_size"], 7)(0)
        batch = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=job.rows), tuple(host))
        job.feed = lambda index, batch=batch: batch
        if hasattr(job, "scope_table"):
            try:
                job.scope_table()
                raise SystemExit(f"{name}: scope_table compiled nothing")
            except _Lowered as caught:
                lowered = caught.args[0]
        else:
            def placed(tree_):
                return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=job.replicated), tree_)

            params = placed(jax.eval_shape(lambda: weights.make(config, 0)))
            state = placed(jax.eval_shape(job.opt.init, params))
            lowered = job.step_fn.lower(params, state, batch)
        text = lowered.as_text()
        out[name] = {"sha256": hashlib.sha256(text.encode()).hexdigest(),
                     "chars": len(text)}
        print(name, out[name], flush=True)
    with open(argv[1], "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main(sys.argv[1:])
