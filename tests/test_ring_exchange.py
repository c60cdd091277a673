"""The gradient exchange as a two-way ring (communicators/ring.py).

Where a train step of ``xla_ici`` has one backward pass, its large float
buckets are reduced as rings — ``lax.ppermute`` hops and ordinary adds,
each hop pinned under that pass (``mean_grads_under``) — instead of one
``lax.psum`` a bucket.  The contract: the float mean of the same terms
(only the order of the additions differs), **bit-identical on every
device** (every element is reduced on one device and copied from there),
and ``psum`` untouched wherever the ring does not engage:
``allreduce_grad`` itself, and a pass with nothing to pin a hop to.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from chainermn_tpu.communicators import (
    build_mesh,
    create_communicator,
    overlap,
    ring,
)
from chainermn_tpu.observability import reporter as reporter_mod

MESHES = {2: (1, 2), 4: (2, 2), 8: (2, 4)}


def _mesh(devices8, world):
    inter, intra = MESHES[world]
    return build_mesh(inter_size=inter, intra_size=intra,
                      devices=devices8[:world])


def _ulps(got, want64, dtype):
    """|got - want| in units of ``dtype``'s spacing at ``want``."""
    want = np.asarray(want64, np.float64)
    eps = float(jnp.finfo(dtype).eps)
    spacing = np.maximum(
        2.0 ** np.floor(np.log2(np.maximum(np.abs(want), 1e-30))) * eps,
        float(jnp.finfo(dtype).tiny))
    return np.abs(np.asarray(got, np.float64) - want) / spacing


def _per_device(mesh, fn, stacked):
    axes = mesh.axis_names
    return np.asarray(jax.jit(jax.shard_map(
        lambda b: fn(b[0])[None], mesh=mesh, in_specs=P(axes),
        out_specs=P(axes), check_vma=False))(stacked))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("extra", [0, 37, -1],
                         ids=["whole", "tail", "no_piece"])
@pytest.mark.parametrize("world", [2, 4, 8])
def test_ring_mean_is_the_mean_on_every_device(devices8, world, extra, dtype):
    """Against a float64 NumPy mean: within 2 ulp (a world of 8 adds
    seven times, each rounding half an ulp of a sum that only grows:
    3.5), and the same bits on every device.  ``tail``: a length that is
    no whole number of pieces; ``no_piece``: shorter than one piece a hop,
    the whole bucket is the tail."""
    mesh = _mesh(devices8, world)
    axes = mesh.axis_names
    whole = 2 * world * ring.PIECE_ALIGN_1D
    size = whole - 5 if extra < 0 else 3 * whole + extra
    assert extra == 0 or size % (2 * world)
    rng = np.random.default_rng(world * 1000 + size)
    stacked = jnp.asarray(rng.random((world, size)) + 0.5, dtype)
    order = ring.ring_order(mesh, axes)
    out = _per_device(
        mesh, lambda b: ring.ring_mean(b, axes, order), stacked)
    assert out.dtype == stacked.dtype and out.shape == stacked.shape
    for r in range(1, world):
        np.testing.assert_array_equal(
            out[r].view(np.uint8), out[0].view(np.uint8),
            err_msg=f"device {r} differs from device 0")
    want = np.asarray(stacked, np.float64).mean(0)
    assert _ulps(out[0], want, dtype).max() <= max(2.0, (world - 1) / 2)


@pytest.mark.parametrize("world", [2, 4, 8])
def test_ring_mean_of_a_leaf_in_its_own_shape(devices8, world):
    """A 2-D gradient leaf is exchanged without a ravel (on the chip a
    ravel of a tiled array is a copy): cut along its rows in pieces of
    whole 8-row tiles, the rows left over through the small ``psum``."""
    mesh = _mesh(devices8, world)
    axes = mesh.axis_names
    rows = 3 * 2 * world * 8 + 5
    assert ring.piece_rows((rows, 40), world) == 24
    stacked = jnp.asarray(
        np.random.default_rng(world).random((world, rows, 40)) + 0.5,
        jnp.float32)
    order = ring.ring_order(mesh, axes)
    out = _per_device(
        mesh, lambda b: ring.ring_mean(b, axes, order), stacked)
    assert out.shape == stacked.shape
    for r in range(1, world):
        np.testing.assert_array_equal(out[r], out[0])
    want = np.asarray(stacked, np.float64).mean(0)
    assert _ulps(out[0], want, "float32").max() <= max(2.0, (world - 1) / 2)


def test_ring_order_follows_the_chips_coordinates():
    """On a 2x2 the ring 0 -> 1 -> 3 -> 2 uses four physical links; mesh
    order would cross a diagonal twice.  Devices that do not say where
    they are keep mesh order."""
    def fake_mesh(devices, shape):
        names = ("inter", "intra")
        return types.SimpleNamespace(
            devices=np.array(devices, dtype=object).reshape(shape),
            axis_names=names, shape=dict(zip(names, shape)))

    chip = lambda *c: types.SimpleNamespace(coords=c)  # noqa: E731
    square = [chip(0, 0, 0), chip(1, 0, 0), chip(0, 1, 0), chip(1, 1, 0)]
    for shape in ((1, 4), (2, 2)):
        assert ring.ring_order(
            fake_mesh(square, shape), ("inter", "intra")) == (0, 1, 3, 2)
    # a 2x4 block: every step of the closed walk is one link
    block = [chip(x, y, 0) for y in range(2) for x in range(4)]
    order = ring.ring_order(fake_mesh(block, (2, 4)), ("inter", "intra"))
    assert sorted(order) == list(range(8))
    for a, b in zip(order, order[1:] + order[:1]):
        assert sum(abs(p - q) for p, q in zip(
            block[a].coords, block[b].coords)) == 1
    # no closed walk over a line of three by one... nor without coords
    line = [chip(x, 0, 0) for x in range(4)]
    assert ring.ring_order(
        fake_mesh(line, (1, 4)), ("inter", "intra")) == (0, 1, 2, 3)
    nowhere = [types.SimpleNamespace() for _ in range(4)]
    assert ring.ring_order(
        fake_mesh(nowhere, (1, 4)), ("inter", "intra")) == (0, 1, 2, 3)


def _tree(size):
    """Two ring-sized float32 leaves, one the same size in int32, and a
    small float32 one."""
    rng = np.random.default_rng(size)
    return {
        "a_big": jnp.asarray(rng.random(size) + 0.5, jnp.float32),
        "b_big": jnp.asarray(rng.random((size // 8, 8)), jnp.float32),
        "c_int": jnp.asarray(rng.integers(-99, 99, size), jnp.int32),
        "d_small": jnp.asarray(rng.random(33), jnp.float32),
    }


def _stacked(tree, n):
    return jax.tree.map(
        lambda l: jnp.stack([l + jnp.asarray(r, l.dtype) for r in range(n)]),
        tree)


def _lowered(comm, stacked):
    def body(tree):
        out = comm.allreduce_grad(jax.tree.map(lambda x: x[0], tree))
        return jax.tree.map(lambda x: x[None], out)

    spec = jax.tree.map(lambda _: P(comm.axes), stacked)
    return jax.jit(comm.shard_map(body, (spec,), spec)).lower(
        stacked).as_text()


@pytest.fixture
def small_ring(monkeypatch):
    """The ring engages from 64 KiB, so that CPU-sized buckets ride it."""
    monkeypatch.setattr(overlap, "RING_MIN_BYTES", 64 * 1024)


def _counters(fn):
    """``fn()``'s result and the Reporter's counters while it ran."""
    rep = reporter_mod.Reporter()
    with reporter_mod.scope(rep):
        out = fn()
    return out, rep.summary()["counters"]


@pytest.mark.parametrize("over", [True, False], ids=["staged", "eager"])
def test_allreduce_grad_keeps_psum_and_counts_it(devices8, small_ring, over):
    """``allreduce_grad`` pins nothing, and a ring nothing pins runs
    after the backward pass, slower than the ``psum`` it replaces: both
    emissions keep ``psum`` (no ``collective-permute`` in the lowered
    text, the staged result the eager one's bits), and the
    ``grad_exchange/*`` counters say so."""
    mesh = _mesh(devices8, 8)
    stacked = _stacked(_tree(48 * 1024), 8)
    make = lambda o: create_communicator(  # noqa: E731
        "xla_ici", mesh=mesh, bucket_bytes=64 * 1024, overlap=o)
    text, counters = _counters(lambda: _lowered(make(over), stacked))
    assert "collective_permute" not in text
    assert counters["grad_exchange/ring_buckets"] == 0
    assert counters["grad_exchange/psum_buckets"] == 4
    assert counters["grad_exchange/ring_bytes"] == 0
    assert counters["grad_exchange/hops"] == 0
    assert counters["grad_pack/buckets"] == 4
    out, ref = (make(o).eager_allreduce_grad(stacked) for o in (over, False))
    for k in stacked:
        np.testing.assert_array_equal(
            np.asarray(out[k]), np.asarray(ref[k]), err_msg=k)


def _mlp(rng):
    params = {"w1": jnp.asarray(rng.normal(size=(64, 512)) * 0.1, jnp.float32),
              "w2": jnp.asarray(rng.normal(size=(512, 64)) * 0.1, jnp.float32),
              "b": jnp.zeros((64,), jnp.float32)}

    def loss_fn(p, batch):
        h = jnp.tanh(batch @ p["w1"])
        out = h @ p["w2"] + p["b"]
        return jnp.mean((out - batch) ** 2), {"mean_out": out.mean()}

    return params, loss_fn


def _rows(seed, rows=1024):
    """128 rows a device of eight: the cotangent of ``h`` (128 x 512) is
    large enough to tie a hop to."""
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=(rows, 64)), jnp.float32)


def _exchange(comm, grads_of, params, x, under=True):
    """Lowered text and result of ``(loss, aux, mean grads, exchanged)``
    a device: through ``mean_grads_under``, or the gradients first and
    ``allreduce_grad`` after them."""
    flags = []

    def body(p, b):
        if under:
            (loss, aux), grads, exchanged = comm.mean_grads_under(
                grads_of, p, b)
            flags.append(exchanged)
            if not exchanged:
                grads = comm.allreduce_grad(grads)
        else:
            (loss, aux), grads = grads_of(p, b)
            grads = comm.allreduce_grad(grads)
        return jax.tree.map(lambda v: v[None], (loss, aux, grads))

    fn = jax.jit(comm.shard_map(body, (P(), P(comm.axes)), P(comm.axes)))
    return fn.lower(params, x).as_text(), fn(params, x), flags


def test_train_step_keeps_its_state_replicated(devices8, small_ring):
    """A whole step with the ring in it: the parameters come back the
    same bits on every device, and within rounding of the step whose
    exchange is the eager ``psum``."""
    import optax

    import chainermn_tpu

    mesh = _mesh(devices8, 8)
    params, loss_aux = _mlp(np.random.default_rng(0))
    x = _rows(5)
    loss_fn = lambda p, batch: loss_aux(p, batch)[0]  # noqa: E731

    def run(over):
        comm = create_communicator(
            "xla_ici", mesh=mesh, bucket_bytes=64 * 1024, overlap=over)
        opt = chainermn_tpu.create_multi_node_optimizer(optax.sgd(0.1), comm)
        step = opt.make_train_step(loss_fn, donate=False)
        p, s = params, opt.init(params)
        for _ in range(2):
            p, s, loss = step(p, s, comm.global_batch(x))
        return p

    (ringed, counters), eager = _counters(lambda: run(True)), run(False)
    assert counters["grad_exchange/ring_buckets"] >= 2  # (a trace: 2)
    for k in params:
        shards = [np.asarray(s.data) for s in ringed[k].addressable_shards]
        assert len(shards) == 8
        for s in shards[1:]:
            np.testing.assert_array_equal(s, shards[0], err_msg=k)
        np.testing.assert_allclose(
            np.asarray(ringed[k]), np.asarray(eager[k]), rtol=1e-5,
            atol=1e-7, err_msg=k)


def test_exchange_under_the_backward_pass_is_the_same_mean(
        devices8, small_ring):
    """``mean_grads_under`` runs the gradient function equation by
    equation, starts a bucket's ring where its last gradient is made and
    ties the hops to the matrix products after it: the lowered text holds
    the barriers and the two large buckets' hops, the ``grad_exchange/*``
    counters read what the plan says, the means are the same bits on
    every device and within float32 rounding of ``allreduce_grad``'s
    ``psum`` of the same gradients (the small bucket, which keeps its
    ``psum``, bit for bit); what the function returns beside the
    gradients comes through untouched."""
    mesh = _mesh(devices8, 8)
    params, loss_fn = _mlp(np.random.default_rng(1))
    x = _rows(2)
    comm = create_communicator("xla_ici", mesh=mesh, bucket_bytes=64 * 1024)
    grads_of = jax.value_and_grad(loss_fn, has_aux=True)

    (text_u, out_u, flags), counters = _counters(
        lambda: _exchange(comm, grads_of, params, x))
    text_a, out_a, _ = _exchange(comm, grads_of, params, x, under=False)
    assert flags == [True]
    assert text_u.count("optimization_barrier") >= 1
    assert text_u.count("collective_permute") == 2 * ring.ring_hops(8)
    assert "optimization_barrier" not in text_a
    assert "collective_permute" not in text_a
    assert counters["grad_exchange/ring_buckets"] == 2
    assert counters["grad_exchange/psum_buckets"] == 1
    assert counters["grad_exchange/ring_bytes"] == 2 * 64 * 512 * 4
    assert counters["grad_exchange/hops"] == 2 * ring.ring_hops(8)
    assert counters["grad_exchange/ties"] >= 1
    assert counters["grad_pack/buckets"] == 3

    (loss_u, aux_u, grads_u), (loss_a, aux_a, grads_a) = out_u, out_a
    for a, b in zip(jax.tree.leaves((loss_u, aux_u, grads_u["b"])),
                    jax.tree.leaves((loss_a, aux_a, grads_a["b"]))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for k in ("w1", "w2"):
        a, b = np.asarray(grads_u[k]), np.asarray(grads_a[k])
        for r in range(1, 8):
            np.testing.assert_array_equal(a[r], a[0], err_msg=k)
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-8, err_msg=k)


def _scanned(rng):
    """Four layers under one ``lax.scan``: the stacked gradients leave the
    backward ``scan`` together, with no matrix product after them."""
    params = {"w": jnp.asarray(rng.normal(size=(4, 128, 128)) * 0.1,
                               jnp.float32),
              "out": jnp.asarray(rng.normal(size=(128, 64)) * 0.1,
                                 jnp.float32)}

    def loss_fn(p, batch):
        h, _ = jax.lax.scan(lambda h, w: (jnp.tanh(h @ w), None),
                            jnp.tile(batch, (1, 2)), p["w"])
        return jnp.mean((h @ p["out"] - batch) ** 2), {}

    return params, loss_fn


@pytest.mark.parametrize("case", [
    "world_of_one", "quantised_wire", "cast_wire", "other_communicator",
    "default_size", "eager_emission", "nothing_large_to_tie",
    "scanned_layers"])
def test_psum_is_untouched_where_the_ring_does_not_engage(
        devices8, small_ring, monkeypatch, case):
    """``mean_grads_under`` hands the gradients back unexchanged — no
    ``collective-permute`` and no barrier in the lowered text, the bits
    of the gradients-then-``allreduce_grad`` program — for a world of
    one, the quantised wire, a cast wire, a communicator with no ring,
    buckets under the size the package ships (these 128 KiB ones), the
    eager emission, a batch whose cotangents are too small to tie a hop
    to, and a backward pass that is one ``scan`` over the layers."""
    kw, name, world, rows = {}, "xla_ici", 8, 1024
    model = _scanned if case == "scanned_layers" else _mlp
    if case == "world_of_one":
        world = 1
    elif case == "quantised_wire":
        kw["comm_dtype"] = "int8"
    elif case == "cast_wire":
        kw["allreduce_grad_dtype"] = jnp.bfloat16
    elif case == "other_communicator":
        name = "hierarchical"
    elif case == "default_size":
        monkeypatch.undo()
        assert overlap.RING_MIN_BYTES > 128 * 1024
    elif case == "eager_emission":
        kw["overlap"] = False
    elif case == "nothing_large_to_tie":
        rows = 32
    mesh = (build_mesh(inter_size=1, intra_size=1, devices=devices8[:1])
            if world == 1 else _mesh(devices8, world))
    params, loss_fn = model(np.random.default_rng(6))
    x = _rows(7, rows)
    comm = create_communicator(name, mesh=mesh, bucket_bytes=64 * 1024, **kw)
    grads_of = jax.value_and_grad(loss_fn, has_aux=True)
    (text_u, out_u, flags), counters = _counters(
        lambda: _exchange(comm, grads_of, params, x))
    text_a, out_a, _ = _exchange(comm, grads_of, params, x, under=False)
    assert flags == [False]
    assert "collective_permute" not in text_u
    assert "optimization_barrier" not in text_u
    assert counters["grad_exchange/ring_buckets"] == 0
    assert "grad_exchange/ties" not in counters
    for a, b in zip(jax.tree.leaves(out_u), jax.tree.leaves(out_a)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_gradient_function_is_traced_once(devices8, small_ring):
    """Whether the ring engages, finds nothing to pin a hop to, or is not
    asked: ``grad_fn`` is traced once (a second trace would double a
    large model's start-up and every trace-time counter in it)."""
    mesh = _mesh(devices8, 8)
    params, loss_fn = _mlp(np.random.default_rng(8))
    grads_of = jax.value_and_grad(loss_fn, has_aux=True)
    for rows, over, want in ((1024, True, True), (32, True, False),
                             (1024, False, False)):
        comm = create_communicator(
            "xla_ici", mesh=mesh, bucket_bytes=64 * 1024, overlap=over)
        seen = []

        def spy(p, b):
            seen.append(type(p["w1"]))
            return grads_of(p, b)

        _, _, flags = _exchange(comm, spy, params, _rows(9, rows))
        # (lowered once and run once: the jit cache holds the trace)
        assert (flags, len(seen)) == ([want], 1), (rows, over)


def _product_after(fillers, rows):
    """A jaxpr: a fresh ``(rows, 256)`` array, ``fillers`` scalar
    equations, then a matrix product that reads it (and an old weight)."""
    def fn(x, w, s):
        y = x * 2.0
        for _ in range(fillers):
            s = s + 1.0
        return y @ w, s

    return jax.make_jaxpr(fn)(
        jnp.zeros((rows, 256)), jnp.zeros((256, 256)), 0.0).jaxpr


@pytest.mark.parametrize("fillers,rows,tied", [
    (overlap.PIN_AGE - 2, 256, True),    # age PIN_AGE - 1: fresh
    (overlap.PIN_AGE - 1, 256, False),   # age PIN_AGE: a residual by now
    (0, overlap.PIN_MIN_ELEMS // 256, True),
    (0, overlap.PIN_MIN_ELEMS // 256 - 1, False),  # too small to matter
])
def test_a_hop_is_tied_to_a_fresh_large_operand_only(fillers, rows, tied):
    """``PIN_AGE`` and ``PIN_MIN_ELEMS``, each on both sides: a matrix
    product is a place to tie a hop to if it reads an array of at least
    ``PIN_MIN_ELEMS`` elements made fewer than ``PIN_AGE`` equations
    before it; the weight, an input of the program, never counts."""
    jaxpr = _product_after(fillers, rows)
    product = [i for i, e in enumerate(jaxpr.eqns)
               if e.primitive.name == "dot_general"]
    assert product == [fillers + 1]
    assert overlap.pin_sites(jaxpr) == (product if tied else [])
    assert overlap.made_at(jaxpr, 0) == [fillers + 1, fillers or -1]


@pytest.mark.parametrize("kind", ["has_aux", "loss_scale", "double_buffering"])
def test_train_step_variants_keep_their_contract_under_the_ring(
        devices8, small_ring, kind):
    """The step's other surfaces with the exchange laid under the
    backward pass: within rounding of the eager ``psum`` step."""
    import optax

    import chainermn_tpu

    mesh = _mesh(devices8, 8)
    params, loss_aux = _mlp(np.random.default_rng(3))
    x = _rows(4)
    loss_only = lambda p, b: loss_aux(p, b)[0]  # noqa: E731

    def run(over):
        comm = create_communicator(
            "xla_ici", mesh=mesh, bucket_bytes=64 * 1024, overlap=over)
        opt = chainermn_tpu.create_multi_node_optimizer(
            optax.sgd(0.1), comm,
            double_buffering=(kind == "double_buffering"))
        step = opt.make_train_step(
            loss_aux if kind == "has_aux" else loss_only, donate=False,
            has_aux=(kind == "has_aux"),
            loss_scale=128.0 if kind == "loss_scale" else None)
        p, s = params, opt.init(params)
        for _ in range(3):
            p, s, *rest = step(p, s, comm.global_batch(x))
        return p, rest

    ((p_ring, rest_ring), counters) = _counters(lambda: run(True))
    assert counters["grad_exchange/ring_buckets"] >= 2  # (a trace: 2)
    assert counters["grad_exchange/ties"] >= 1
    p_eager, rest_eager = run(False)
    for a, b in zip(jax.tree.leaves((p_ring, rest_ring)),
                    jax.tree.leaves((p_eager, rest_eager))):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7)
