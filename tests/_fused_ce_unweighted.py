"""What ``fused_cross_entropy`` traces to WITHOUT weights — the loss head
of every cell but the block-diffusion one.  For each case the sha256 of
the JAXPR of its value and gradients (the chunked scan, its carries and
the backward rule's scaling are all in it).

    PYTHONPATH=. JAX_PLATFORMS=cpu python tests/_fused_ce_unweighted.py out.json

writes them; run in a checkout of the commit BEFORE a change to
``ops/fused_ce.py``, it gives what ``tests/test_fused_ce_weights.py``
holds the change to (``tests/golden/fused_ce_unweighted.json``, from the
parent of PR 47, which gave the scan its weights).
"""

import hashlib
import importlib
import json
import re
import sys

import jax
import jax.numpy as jnp

ce = importlib.import_module("chainermn_tpu.ops.fused_ce")

#: name -> (rows, d, vocab, chunk, hidden dtype)
CASES = {
    "tied-bf16": (256, 64, 512, 64, jnp.bfloat16),
    "untied-f32-ragged": (192, 32, 211, 128, jnp.float32),
    "default-chunk": (1024, 32, 96, None, jnp.bfloat16),
}


def record(case):
    rows, d, vocab, chunk, dtype = CASES[case]
    h = jax.ShapeDtypeStruct((2, rows // 2, d), dtype)
    e = jax.ShapeDtypeStruct((vocab, d), jnp.float32)
    labels = jax.ShapeDtypeStruct((2, rows // 2), jnp.int32)

    def loss(h, e, labels):
        return ce.fused_cross_entropy(h, e, labels, chunk=chunk)

    out = {}
    for name, fn in (("loss", loss),
                     ("grads", jax.value_and_grad(loss, (0, 1)))):
        text = re.sub(r"0x[0-9a-f]+", "0x",
                      str(jax.make_jaxpr(fn)(h, e, labels)))
        out[name] = hashlib.sha256(text.encode()).hexdigest()
    return out


if __name__ == "__main__":
    with open(sys.argv[1], "w") as f:
        json.dump({case: record(case) for case in CASES}, f, indent=1,
                  sort_keys=True)
        f.write("\n")
