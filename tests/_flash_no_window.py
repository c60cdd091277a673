"""What the flash kernels' wrappers trace and lower to WITHOUT a window —
the path every cell but the sliding rows runs.  For each case: the grids
of its ``pallas_call``s, the sha256 of its JAXPR (kernel bodies, index
maps and grids are all in it) and the sha256 of its text lowered for a
TPU with locations and the Mosaic payloads out (a payload holds the
source lines of the kernel, which move with any edit above it; the JAXPR
digest is what holds the kernel itself).

    PYTHONPATH=. JAX_PLATFORMS=cpu python tests/_flash_no_window.py out.json

writes them; run in a checkout of the commit BEFORE a change to
``ops/flash_attention.py``, it gives what
``tests/test_flash_attention.py`` holds the change to
(``tests/golden/flash_no_window.json``: the five ``fwd`` records from
the parent of PR 40).  A change that is MEANT to move this path remakes
its records on the changed tree and shows that the others kept theirs:
the five ``bwd`` records are PR 48's, whose backward is one
``pallas_call`` on the forward's grid where the parent ran two (``bwd``
goes through ``_flash_bh_bwd``, so it records the side the footprint
rule gives rows this short: the one pass).  PR 50 (the backward's
1024-edge diagonal tiles by halves, a windowed row's guard by row) moved
none of them — no case here has a window or an edge that halves — and
added ``blockdiff``, a call under the block-diffusion mask, recorded in
that PR's PARENT checkout (335fd5b, with this script) and held to it.
"""

import hashlib
import importlib
import json
import re
import sys

import jax
import jax.numpy as jnp

fa = importlib.import_module("chainermn_tpu.ops.flash_attention")

#: name -> (BH, BHk, Sq, Sk, D, block_q, block_k, causal, segmented, dlse
#: [, block-diffusion (L, B)])
CASES = {
    "causal": (4, 4, 256, 256, 64, 64, 64, True, False, False),
    "gqa-rectangular": (4, 2, 256, 256, 64, 64, 128, True, False, False),
    "full": (2, 2, 256, 256, 128, 128, 64, False, False, False),
    "segments": (4, 2, 256, 256, 64, 128, 64, True, True, False),
    "more-keys-dlse": (4, 1, 128, 256, 64, 32, 64, True, False, True),
    "blockdiff": (4, 2, 512, 512, 64, 64, 128, True, False, False, (256, 4)),
}


def programs(case, interpret):
    """``{"fwd": (fn, operands), "bwd": (fn, operands)}`` of one case, the
    operands as shapes."""
    BH, BHk, Sq, Sk, D, bq, bk, causal, segmented, dlse, *mask = CASES[case]
    geometry = dict(scale=D ** -0.5, causal=causal, block_q=bq, block_k=bk,
                    interpret=interpret)
    if mask:
        geometry["blockdiff"] = mask[0]

    def arr(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype)

    q, k = arr(BH, Sq, D), arr(BHk, Sk, D)
    segs = {}
    if segmented:
        segs = {"q_seg": arr(BH, Sq, 1, dtype=jnp.int32),
                "kv_seg": arr(BHk, Sk, 1, dtype=jnp.int32)}
    extra = dict(segs)
    if dlse:
        extra["dlse"] = arr(BH, Sq, dtype=jnp.float32)

    def fwd(q, k, v, segs):
        return fa._flash_bh_fwd(q, k, v, **geometry, **segs)

    def bwd(q, k, v, o, lse, do, extra):
        return fa._flash_bh_bwd(q, k, v, o, lse, do, **geometry, **extra)

    lse = arr(BH, Sq, 1, dtype=jnp.float32)
    return {"fwd": (fwd, (q, k, k, segs)),
            "bwd": (bwd, (q, k, k, q, lse, q, extra))}


def grids(jaxpr):
    """The ``grid`` of every ``pallas_call`` in ``jaxpr``, in order."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(list(eqn.params["grid_mapping"].grid))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out.extend(grids(sub))
    return out


def _sha(text):
    return hashlib.sha256(text.encode()).hexdigest()


def lowered_text(fn, operands):
    """``fn`` lowered for a TPU, locations and Mosaic payloads out."""
    text = jax.jit(fn).trace(*operands).lower(
        lowering_platforms=("tpu",)).as_text()
    text = re.sub(r" loc\(.*?\)", "", text)
    return re.sub(r'\\22body\\22: \\22[^\\]*\\22', "body", text)


def record(case):
    out = {}
    for which, (fn, operands) in programs(case, interpret=False).items():
        traced = jax.make_jaxpr(fn)(*operands)
        out[which] = {
            "grids": grids(traced.jaxpr),
            # (an object's address in a parameter's repr is the process's)
            "jaxpr": _sha(re.sub(r"0x[0-9a-f]+", "0x", str(traced))),
            "lowered": _sha(lowered_text(fn, operands))}
    return out


if __name__ == "__main__":
    with open(sys.argv[1], "w") as f:
        json.dump({case: record(case) for case in CASES}, f, indent=1,
                  sort_keys=True)
        f.write("\n")
