"""Digests of what the five older families compute — the GPT-2-style row
and the ``granitemoehybrid``, ``nemotron_h``, ``zaya`` and ``qwen3_next``
tables at the benchmark's tiny test sizes: the sha256 of the JAXPR of a
model's float32 logits and gradient, through the flash adapter (interpret
mode on the CPU) under ``remat``.  The traced program and not its output:
XLA:CPU sums in an order that follows the machine's core count, so an
output's bits are one machine's, while two checkouts that trace the same
equations over the same constants compute the same bits anywhere.

    PYTHONPATH=. JAX_PLATFORMS=cpu python tests/_older_families.py out.json

writes them; run in a checkout of the commit before a change to the shared
attention path and the table, it gives what ``tests/test_mellum2.py``
holds the change to, to the bit (``tests/golden/older_families.json``).
Imports nothing a checkout from before PR 39 lacks.

The file's five top-level digests are remade on the CHANGED tree by a
change that means to move a family's program (PR 48 remade all five:
every family's gradient runs the flash backward, one kernel since).
Its ``two_kernels`` group is what the five were before PR 48 (PR 41's
for ``zaya`` and ``qwen3_next``, PR 39's parent's for the three others):
the program with the backward as ``flash-bwd-dq`` + ``flash-bwd-dkv``,
which the two-kernel side of the footprint rule still has to trace.
"""

import hashlib
import json
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np


def tables():
    """``{family: (BlockTable, d_model, vocab)}``."""
    from chainermn_tpu.models.block_table import (gpt2_table,
                                                  table_from_config)
    from chipbench.runners import (train_cca_moe, train_gdn_moe,
                                   train_moe_hybrid)
    from chipbench.tests import (tiny_cca_moe, tiny_gdn_moe, tiny_hybrid,
                                 tiny_moe_hybrid)

    c = tiny_hybrid.CONFIG
    out = {"gpt2": (gpt2_table(2, 4, 128, n_kv_heads=2), 64, 211),
           "granitemoehybrid": (
               table_from_config(c, n_layers=c["n_layer"]),
               c["hidden_size"], c["vocab_size"])}
    for name, runner, tiny in (
            ("nemotron_h", train_moe_hybrid, tiny_moe_hybrid),
            ("zaya", train_cca_moe, tiny_cca_moe),
            ("qwen3_next", train_gdn_moe, tiny_gdn_moe)):
        c = tiny.CONFIG
        out[name] = (runner.build_table(c), c["hidden_size"],
                     c["vocab_size"])
    return out


def digest(table, d_model, vocab, text_of=str):
    """``text_of``: the traced program as the text that is hashed
    (``tests/test_mellum2.py`` reads the two-kernel backward's program
    under the name its wrapper had when ``two_kernels`` was recorded)."""
    from chainermn_tpu.models.transformer import TransformerLM
    from chainermn_tpu.ops import make_flash_attention_fn

    lm = TransformerLM(vocab=vocab, d_model=d_model, table=table,
                       max_len=128, dtype=jnp.float32, remat=True,
                       attention_fn=make_flash_attention_fn(
                           causal=True, scale=next(
                               (r.attn_scale for r in table.layers
                                if r.mixer == "attention"), None)))
    toks = jax.random.randint(jax.random.PRNGKey(7), (2, 129), 0, vocab)
    x, y = toks[:, :-1], toks[:, 1:]
    params = jax.eval_shape(
        lambda: lm.init(jax.random.PRNGKey(3), x)["params"])

    def loss(p):
        z = lm.apply({"params": p}, x)
        picked = jnp.take_along_axis(z, y[..., None], axis=-1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(z, axis=-1) - picked), z

    traced = jax.make_jaxpr(jax.value_and_grad(loss, has_aux=True))(params)
    # (an object's address in a parameter's repr is the process's own)
    h = hashlib.sha256(
        re.sub(r"0x[0-9a-f]+", "0x", text_of(traced)).encode())
    for const in traced.consts:     # rotary tables, masks, multipliers
        h.update(np.asarray(const).tobytes())
    return h.hexdigest()


def digests():
    return {name: digest(*args) for name, args in tables().items()}


if __name__ == "__main__":
    with open(sys.argv[1], "w") as f:
        json.dump(digests(), f, indent=1, sort_keys=True)
        f.write("\n")
