"""The gradient exchange compiled for four DESCRIBED v5e chips (no chip
attached): the mechanism of ``communicators/ring.py`` and its trap,
guarded where no chip is.

libtpu 0.0.34 compiles ``lax.psum`` to a synchronous ``all-reduce``;
a ``lax.ppermute`` stays a ``collective-permute-start`` / ``-done`` pair.
The trap: a bucket viewed as ``(n, chunk)`` is tiled over both
dimensions and every piece cut out of it costs a relayout ``while`` loop
— a 2-layer step went from 13 s / 54 MB of generated code to 142 s /
440 MB (ISSUE 45).  The bucket stays 1-D, and these tests hold it there.
"""

import re

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import chainermn_tpu
from _tpu_compile import four_chips  # noqa: F401  (a fixture)
from chainermn_tpu.communicators import (
    build_mesh,
    create_communicator,
    overlap,
    ring,
)
from chainermn_tpu.models.transformer import TransformerLM

SYNC = " all-reduce("
ENTRY_OP = re.compile(
    r"^\s+(?:ROOT )?%?([\w.\-]+) = .*?\s([a-z][a-z\-]*)\(", re.M)


def _entry(text):
    """The scheduled entry computation: ``[(name, opcode, line)]``."""
    body = text[text.index("\nENTRY "):]
    return [(m.group(1), m.group(2), m.group(0) + body[m.end():body.index(
        "\n", m.end())]) for m in ENTRY_OP.finditer(body)]


@pytest.fixture
def ring_from_4mib(monkeypatch):
    """These widths' FFN matrices are 4 MiB: the ring engages from there
    (the package ships 16 MiB, the four-chip cell's smallest)."""
    monkeypatch.setattr(overlap, "RING_MIN_BYTES", 4 << 20)


def _step(devices, overlap):
    mesh = build_mesh(inter_size=1, intra_size=4, devices=devices)
    comm = create_communicator(
        "xla_ici", mesh=mesh, bucket_bytes=4 << 20, overlap=overlap)
    model = TransformerLM(vocab=8192, d_model=512, n_heads=4, d_ff=2048,
                          n_layers=2, max_len=512)

    def loss_fn(p, batch):
        tokens, labels = batch
        logits = model.apply({"params": p}, tokens).astype(jnp.float32)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()

    opt = chainermn_tpu.create_multi_node_optimizer(optax.adamw(3e-4), comm)
    step = opt.make_train_step(loss_fn, donate=True)
    everywhere = NamedSharding(mesh, P())
    tokens = jax.ShapeDtypeStruct(
        (8, 512), jnp.int32, sharding=NamedSharding(mesh, P(mesh.axis_names)))
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((2, 512), jnp.int32))["params"])
    state = jax.eval_shape(
        lambda p: opt.init(p, _skip_broadcast=True), params)
    put = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=everywhere), tree)
    return step.lower(put(params), put(state), (tokens, tokens)).compile()


def test_ring_alone_is_twelve_permutes_and_no_relayout_loop(four_chips):
    """One 64 MB bucket: 4 (n - 1) asynchronous collective-permutes over
    the 2x2's four links (0 -> 1 -> 3 -> 2), no ``while`` loop, no
    synchronous all-reduce, and temporaries of about a bucket."""
    mesh = build_mesh(inter_size=1, intra_size=4, devices=four_chips)
    axes = mesh.axis_names
    order = ring.ring_order(mesh, axes)
    assert order == (0, 1, 3, 2)
    elems = 16 << 20
    fn = jax.jit(jax.shard_map(
        lambda b: ring.ring_mean(b[0], axes, order)[None], mesh=mesh,
        in_specs=P(axes), out_specs=P(axes), check_vma=False))
    compiled = fn.lower(jax.ShapeDtypeStruct(
        (4, elems), jnp.float32,
        sharding=NamedSharding(mesh, P(axes)))).compile()
    text = compiled.as_text()
    assert text.count(" collective-permute-start(") == ring.ring_hops(4)
    assert text.count(" collective-permute-done(") == ring.ring_hops(4)
    assert " while(" not in text and SYNC not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 3.5 * elems * 4


def test_step_lays_the_hops_under_the_backward_pass(
        four_chips, ring_from_4mib):
    """The step with one backward pass: every hop is a start / done pair, the matrix
    products of the backward pass are scheduled between starts and their
    dones, and what is left for a synchronous all-reduce is under
    ``RING_MIN_BYTES`` a bucket (the loss, the norms, these widths'
    1 MiB projections)."""
    ops = _entry(_step(four_chips, overlap=True).as_text())
    at = {name: k for k, (name, _, _) in enumerate(ops)}
    pairs = [(at[re.search(r"collective-permute-done\(%?([\w.\-]+)\)",
                           line).group(1)], k)
             for k, (_, op, line) in enumerate(ops)
             if op == "collective-permute-done"]
    assert len(pairs) >= 5 * ring.ring_hops(4)  # FFN matrices, embedding
    under = [sum(1 for _, op, line in ops[s + 1:d]
                 if op in ("fusion", "convolution", "custom-call")
                 and "fwd-bwd/transpose" in line)
             for s, d in pairs]
    assert sum(1 for n in under if n) >= len(pairs) // 3, under
    for _, op, line in ops:
        if op == "all-reduce":
            shapes = re.findall(r"f32\[(\d+)\]", line.split(" all-reduce(")[0])
            assert shapes and all(
                int(s) * 4 < overlap.RING_MIN_BYTES for s in shapes), line
    assert not any(op == "while" for _, op, _ in ops)


def test_ring_step_costs_no_more_code_than_the_eager_step(
        four_chips, ring_from_4mib):
    """``overlap=False`` is the parent's eager ``psum`` program: no
    collective-permute in it; and the ring step's generated code stays
    within 2x of it (the trap made it 8x)."""
    ring_step = _step(four_chips, overlap=True)
    eager = _step(four_chips, overlap=False)
    assert "collective-permute" not in eager.as_text()
    assert SYNC in eager.as_text()
    code = lambda c: c.memory_analysis().generated_code_size_in_bytes  # noqa: E731
    assert code(ring_step) < 2 * code(eager), (code(ring_step), code(eager))
