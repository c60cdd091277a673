"""Multi-node optimizer tests, shaped like the reference's
tests/optimizer_tests (SURVEY §4): the distributed update must match the
single-device oracle computing on the full (unsharded) batch, and the
double-buffering variant must apply one-step-stale means.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from chainermn_tpu.communicators import create_communicator
from chainermn_tpu.optimizers import create_multi_node_optimizer


def loss_fn(params, batch):
    x, y = batch
    pred = x @ params["w"] + params["b"]
    return jnp.mean((pred - y) ** 2)


def make_problem(seed=0, n=64, d=4):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(n, d), jnp.float32)
    y = jnp.asarray(rng.randn(n, 1), jnp.float32)
    params = {
        "w": jnp.asarray(rng.randn(d, 1), jnp.float32),
        "b": jnp.zeros((1,), jnp.float32),
    }
    return params, (x, y)


@pytest.mark.parametrize("name", ["naive", "xla_ici", "hierarchical", "two_dimensional"])
def test_matches_single_device_sgd(mesh, name):
    comm = create_communicator(name, mesh=mesh)
    params, batch = make_problem()

    opt = create_multi_node_optimizer(optax.sgd(0.1), comm)
    state = opt.init(params)
    step = opt.make_train_step(loss_fn, donate=False)

    # Oracle: plain full-batch SGD on one device.
    ref_opt = optax.sgd(0.1)
    ref_state = ref_opt.init(params)
    ref_params = params
    cur = params
    for _ in range(3):
        cur, state, loss = step(cur, state, batch)
        g = jax.grad(loss_fn)(ref_params, batch)
        up, ref_state = ref_opt.update(g, ref_state, ref_params)
        ref_params = optax.apply_updates(ref_params, up)

    for k in params:
        np.testing.assert_allclose(
            np.asarray(cur[k]), np.asarray(ref_params[k]), rtol=1e-5, atol=1e-6
        )


def test_loss_is_global_mean(mesh):
    comm = create_communicator("naive", mesh=mesh)
    params, batch = make_problem()
    opt = create_multi_node_optimizer(optax.sgd(0.0), comm)
    state = opt.init(params)
    step = opt.make_train_step(loss_fn, donate=False)
    _, _, loss = step(params, state, batch)
    np.testing.assert_allclose(
        float(loss), float(loss_fn(params, batch)), rtol=1e-5
    )


def test_double_buffering_is_one_step_stale(mesh):
    comm = create_communicator("xla_ici", mesh=mesh)
    params, batch = make_problem()

    opt = create_multi_node_optimizer(optax.sgd(0.1), comm, double_buffering=True)
    state = opt.init(params)
    step = opt.make_train_step(loss_fn, donate=False)

    # Step 0: allreduce only, no parameter change (reference first-call rule).
    p1, state, _ = step(params, state, batch)
    for k in params:
        np.testing.assert_allclose(np.asarray(p1[k]), np.asarray(params[k]))

    # Step 1 applies step 0's gradients.
    p2, state, _ = step(p1, state, batch)
    g0 = jax.grad(loss_fn)(params, batch)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(p2[k]),
            np.asarray(params[k]) - 0.1 * np.asarray(g0[k]),
            rtol=1e-5, atol=1e-6,
        )


def test_imperative_parity_api(mesh):
    comm = create_communicator("naive", mesh=mesh)
    params, batch = make_problem()
    opt = create_multi_node_optimizer(optax.adam(1e-2), comm)
    opt.setup(params, loss_fn)
    losses = [float(opt.update(batch)) for _ in range(5)]
    assert losses[-1] < losses[0]
    assert opt.t == 5
    assert opt.target is not None


def test_adam_with_flax_model(mesh):
    import flax.linen as nn

    from chainermn_tpu.models import MLP

    comm = create_communicator("xla_ici", mesh=mesh)
    model = MLP(n_units=32, n_out=10)
    rng = jax.random.PRNGKey(0)
    x = jax.random.normal(rng, (16, 28, 28))
    y = jax.random.randint(rng, (16,), 0, 10)
    params = model.init(rng, x)

    def ce_loss(params, batch):
        x, y = batch
        logits = model.apply(params, x)
        return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

    opt = create_multi_node_optimizer(optax.adam(1e-3), comm)
    state = opt.init(params)
    step = opt.make_train_step(ce_loss, donate=False)
    l0 = None
    for i in range(10):
        params, state, loss = step(params, state, (x, y))
        if i == 0:
            l0 = float(loss)
    assert float(loss) < l0


@pytest.mark.parametrize("opt_name", ["sgd", "adam"])
def test_zero1_matches_replicated(mesh, opt_name):
    """ZeRO-1 (sharded optimizer state) must produce the SAME parameter
    trajectory as the replicated optimizer."""
    make_opt = lambda: optax.sgd(0.1, momentum=0.9) if opt_name == "sgd" else optax.adam(1e-2)
    params, batch = make_problem()

    comm = create_communicator("xla_ici", mesh=mesh)
    z_opt = create_multi_node_optimizer(make_opt(), comm, zero_stage=1)
    z_state = z_opt.init(params)
    z_step = z_opt.make_train_step(loss_fn, donate=False)

    r_opt = create_multi_node_optimizer(make_opt(), comm)
    r_state = r_opt.init(params)
    r_step = r_opt.make_train_step(loss_fn, donate=False)

    zp, rp = params, params
    for _ in range(4):
        zp, z_state, z_loss = z_step(zp, z_state, batch)
        rp, r_state, r_loss = r_step(rp, r_state, batch)

    for k in params:
        np.testing.assert_allclose(
            np.asarray(zp[k]), np.asarray(rp[k]), rtol=1e-5, atol=1e-6
        )
    np.testing.assert_allclose(float(z_loss), float(r_loss), rtol=1e-5)

    # The memory claim: inner-state vector leaves are 1/n-sized shards.
    n = comm.device_size
    total = sum(l.size for l in jax.tree.leaves(params))
    shard = -(-total // n)
    vec_leaves = [
        l for l in jax.tree.leaves(z_state.inner)
        if getattr(l, "ndim", 0) == 1
    ]
    if opt_name == "adam":
        assert vec_leaves and all(l.shape[0] == shard * n for l in vec_leaves)
        # Global (sharded) buffer: n*shard total, i.e. ~1/n per device.


@pytest.mark.parametrize("n_accum", [2, 4])
def test_grad_accumulation_matches_full_batch(mesh, n_accum):
    """Equal-size microbatches: mean-of-means == full-batch mean, so the
    accumulated trajectory must match the unaccumulated one exactly."""
    params, batch = make_problem()
    comm = create_communicator("xla_ici", mesh=mesh)

    a_opt = create_multi_node_optimizer(optax.sgd(0.1, momentum=0.9), comm)
    a_state = a_opt.init(params)
    a_step = a_opt.make_train_step(loss_fn, donate=False, n_accum=n_accum)

    r_opt = create_multi_node_optimizer(optax.sgd(0.1, momentum=0.9), comm)
    r_state = r_opt.init(params)
    r_step = r_opt.make_train_step(loss_fn, donate=False)

    ap, rp = params, params
    for _ in range(3):
        ap, a_state, a_loss = a_step(ap, a_state, batch)
        rp, r_state, r_loss = r_step(rp, r_state, batch)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(ap[k]), np.asarray(rp[k]), rtol=1e-5, atol=1e-6
        )
    np.testing.assert_allclose(float(a_loss), float(r_loss), rtol=1e-5)


def test_grad_accumulation_rejects_indivisible(mesh):
    params, batch = make_problem(n=64)
    comm = create_communicator("xla_ici", mesh=mesh)
    opt = create_multi_node_optimizer(optax.sgd(0.1), comm)
    state = opt.init(params)
    step = opt.make_train_step(loss_fn, donate=False, n_accum=3)
    with pytest.raises(ValueError, match="divisible"):
        step(params, state, batch)  # 64 % (8*3) != 0


def test_loss_scale_invariant_for_sgd(mesh):
    """SGD is linear in the gradients, so scale-then-unscale must be exact
    (loss reported unscaled)."""
    params, batch = make_problem()
    comm = create_communicator("xla_ici", mesh=mesh)

    s_opt = create_multi_node_optimizer(optax.sgd(0.1), comm)
    s_state = s_opt.init(params)
    s_step = s_opt.make_train_step(loss_fn, donate=False, loss_scale=1024.0)

    r_opt = create_multi_node_optimizer(optax.sgd(0.1), comm)
    r_state = r_opt.init(params)
    r_step = r_opt.make_train_step(loss_fn, donate=False)

    sp, rp = params, params
    for _ in range(3):
        sp, s_state, s_loss = s_step(sp, s_state, batch)
        rp, r_state, r_loss = r_step(rp, r_state, batch)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(sp[k]), np.asarray(rp[k]), rtol=1e-4, atol=1e-5
        )
    np.testing.assert_allclose(float(s_loss), float(r_loss), rtol=1e-5)


@pytest.mark.parametrize("opt_name", ["sgd", "adam"])
def test_zero2_matches_zero1_under_accumulation(mesh, opt_name):
    """ZeRO-2's per-microbatch reduce-scatter accumulation must produce the
    same trajectory as ZeRO-1's full-tree accumulation."""
    make_opt = (
        lambda: optax.sgd(0.1, momentum=0.9)
        if opt_name == "sgd"
        else optax.adam(1e-2)
    )
    params, batch = make_problem()
    comm = create_communicator("xla_ici", mesh=mesh)

    p1, p2 = params, params
    o1 = create_multi_node_optimizer(make_opt(), comm, zero_stage=1)
    s1 = o1.init(params)
    st1 = o1.make_train_step(loss_fn, donate=False, n_accum=2)
    o2 = create_multi_node_optimizer(make_opt(), comm, zero_stage=2)
    s2 = o2.init(params)
    st2 = o2.make_train_step(loss_fn, donate=False, n_accum=2)

    for _ in range(4):
        p1, s1, l1 = st1(p1, s1, batch)
        p2, s2, l2 = st2(p2, s2, batch)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(p1[k]), np.asarray(p2[k]), rtol=1e-5, atol=1e-6
        )
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)


@pytest.mark.parametrize("opt_name", ["sgd", "adam"])
def test_zero3_matches_replicated(mesh, opt_name):
    """ZeRO-3 (sharded master params) must track the replicated trajectory;
    the resident flat buffer must be 1/n per device."""
    make_opt = (
        lambda: optax.sgd(0.1, momentum=0.9)
        if opt_name == "sgd"
        else optax.adam(1e-2)
    )
    params, batch = make_problem()
    comm = create_communicator("xla_ici", mesh=mesh)

    z_opt = create_multi_node_optimizer(make_opt(), comm, zero_stage=3)
    z_state = z_opt.init(params)
    flat = z_opt.shard_params(params)
    z_step = z_opt.make_train_step(loss_fn, donate=False)

    r_opt = create_multi_node_optimizer(make_opt(), comm)
    r_state = r_opt.init(params)
    r_step = r_opt.make_train_step(loss_fn, donate=False)

    rp = params
    for _ in range(4):
        flat, z_state, z_loss = z_step(flat, z_state, batch)
        rp, r_state, r_loss = r_step(rp, r_state, batch)

    zp = z_opt.materialize(flat)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(zp[k]), np.asarray(rp[k]), rtol=1e-5, atol=1e-6
        )
    np.testing.assert_allclose(float(z_loss), float(r_loss), rtol=1e-5)

    # Sharding claim: the flat master buffer is split across all devices.
    n = comm.device_size
    total = sum(l.size for l in jax.tree.leaves(params))
    assert flat.size == -(-total // n) * n
    assert len({s.device for s in flat.addressable_shards}) == n
    assert all(s.data.size == flat.size // n for s in flat.addressable_shards)


def test_zero3_with_grad_accum_and_rng(mesh):
    """Stage 3 composes with n_accum and per-step rng (smoke + descent)."""
    params, batch = make_problem(n=64)
    comm = create_communicator("xla_ici", mesh=mesh)

    def noisy_loss(p, b, key):
        x, y = b
        pred = x @ p["w"] + p["b"]
        return jnp.mean((pred - y) ** 2) + 0.0 * jax.random.normal(key, ())

    opt = create_multi_node_optimizer(optax.adam(1e-2), comm, zero_stage=3)
    state = opt.init(params)
    flat = opt.shard_params(params)
    step = opt.make_train_step(
        noisy_loss, donate=False, n_accum=2, rng=jax.random.PRNGKey(0)
    )
    l0 = None
    for i in range(10):
        flat, state, loss = step(flat, state, batch)
        if i == 0:
            l0 = float(loss)
    assert float(loss) < l0


def test_zero3_setup_supported(mesh):
    """setup()/update() under zero_stage=3 (r4: the imperative surface
    carries the full feature matrix): update trains, target materializes
    the sharded master buffer back to the tree shape."""
    comm = create_communicator("xla_ici", mesh=mesh)
    opt = create_multi_node_optimizer(optax.sgd(0.1), comm, zero_stage=3)
    params, batch = make_problem()
    opt.setup(params, loss_fn)
    l0 = float(opt.update(batch))
    for _ in range(3):
        l1 = float(opt.update(batch))
    assert l1 < l0
    tgt = opt.target
    assert jax.tree.structure(tgt) == jax.tree.structure(params)


def test_zero3_materialize_is_cached(mesh):
    """Repeated materialize/shard_params must reuse one jitted fn, not
    rebuild (and recompile) a fresh closure per call."""
    comm = create_communicator("xla_ici", mesh=mesh)
    opt = create_multi_node_optimizer(optax.sgd(0.1), comm, zero_stage=3)
    params, _ = make_problem()
    flat = opt.shard_params(params)
    opt.materialize(flat)
    assert len(opt._z3_jit) == 2
    flat2 = opt.shard_params(params)
    opt.materialize(flat2)
    assert len(opt._z3_jit) == 2  # cache hit, no new entries


@pytest.mark.parametrize("zero_stage", [1, 2, 3])
def test_double_buffering_with_zero(mesh, zero_stage):
    """VERDICT r1 item 10: double buffering composes with every ZeRO stage
    — the trajectory must equal the replicated double-buffered oracle
    (staleness semantics are sharding-independent), with the stale buffer
    held as a 1/n gradient shard."""
    comm = create_communicator("xla_ici", mesh=mesh)
    params, batch = make_problem()

    r_opt = create_multi_node_optimizer(
        optax.adam(1e-2), comm, double_buffering=True
    )
    r_state = r_opt.init(params)
    r_step = r_opt.make_train_step(loss_fn, donate=False)

    z_opt = create_multi_node_optimizer(
        optax.adam(1e-2), comm, double_buffering=True, zero_stage=zero_stage
    )
    z_state = z_opt.init(params)
    z_step = z_opt.make_train_step(loss_fn, donate=False)
    zp = z_opt.shard_params(params) if zero_stage == 3 else params

    rp = params
    for _ in range(4):
        rp, r_state, r_loss = r_step(rp, r_state, batch)
        zp, z_state, z_loss = z_step(zp, z_state, batch)
    zp_tree = z_opt.materialize(zp) if zero_stage == 3 else zp
    for k in params:
        np.testing.assert_allclose(
            np.asarray(zp_tree[k]), np.asarray(rp[k]), rtol=1e-5, atol=1e-6
        )
    np.testing.assert_allclose(float(z_loss), float(r_loss), rtol=1e-5)
    # The stale buffer really is shard-sized (sharded over the world), not
    # a replicated full gradient tree.
    n, _, shard_size = z_opt._zero_geometry(params)
    assert z_state.comm_buf.shape == (shard_size * n,)


@pytest.mark.parametrize("zero_stage", [1, 3])
def test_with_model_state_zero(mesh, zero_stage):
    """VERDICT r1 item 10: the with-model-state step composes with ZeRO —
    trajectory and model-state statistics match the replicated oracle."""
    comm = create_communicator("xla_ici", mesh=mesh)
    params, batch = make_problem()
    model_state = {"running": jnp.zeros((1,), jnp.float32)}

    def sloss(params, mstate, b):
        x, y = b
        pred = x @ params["w"] + params["b"]
        new_state = {"running": mstate["running"] * 0.9 + 0.1 * jnp.mean(pred)}
        return jnp.mean((pred - y) ** 2), new_state

    r_opt = create_multi_node_optimizer(optax.adam(1e-2), comm)
    r_state = r_opt.init(params)
    r_step = r_opt.make_train_step_with_state(sloss, donate=False)

    z_opt = create_multi_node_optimizer(
        optax.adam(1e-2), comm, zero_stage=zero_stage
    )
    z_state = z_opt.init(params)
    z_step = z_opt.make_train_step_with_state(sloss, donate=False)
    zp = z_opt.shard_params(params) if zero_stage == 3 else params

    rp, rm = params, model_state
    zm = model_state
    for _ in range(3):
        rp, r_state, rm, r_loss = r_step(rp, r_state, rm, batch)
        zp, z_state, zm, z_loss = z_step(zp, z_state, zm, batch)
    zp_tree = z_opt.materialize(zp) if zero_stage == 3 else zp
    for k in params:
        np.testing.assert_allclose(
            np.asarray(zp_tree[k]), np.asarray(rp[k]), rtol=1e-5, atol=1e-6
        )
    np.testing.assert_allclose(
        np.asarray(zm["running"]), np.asarray(rm["running"]), rtol=1e-5
    )
    np.testing.assert_allclose(float(z_loss), float(r_loss), rtol=1e-5)


def test_double_buffering_with_model_state(mesh):
    """Double buffering + mutable model state: params follow the one-step
    -stale rule while BatchNorm-style statistics update from the CURRENT
    step."""
    comm = create_communicator("xla_ici", mesh=mesh)
    params, batch = make_problem()
    model_state = {"running": jnp.zeros((1,), jnp.float32)}

    def sloss(params, mstate, b):
        x, y = b
        pred = x @ params["w"] + params["b"]
        new_state = {"running": mstate["running"] * 0.9 + 0.1 * jnp.mean(pred)}
        return jnp.mean((pred - y) ** 2), new_state

    opt = create_multi_node_optimizer(
        optax.sgd(0.1), comm, double_buffering=True
    )
    state = opt.init(params)
    step = opt.make_train_step_with_state(sloss, donate=False)

    # Step 0: reduce-only — params unchanged, model state DOES update.
    p1, state, m1, _ = step(params, state, model_state, batch)
    for k in params:
        np.testing.assert_allclose(np.asarray(p1[k]), np.asarray(params[k]))
    assert float(jnp.abs(m1["running"]).sum()) > 0

    # Step 1 applies step 0's gradients.
    p2, state, m2, _ = step(p1, state, m1, batch)
    g0 = jax.grad(lambda p: sloss(p, model_state, batch)[0])(params)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(p2[k]),
            np.asarray(params[k]) - 0.1 * np.asarray(g0[k]),
            rtol=1e-5, atol=1e-6,
        )


def test_imperative_api_full_feature_matrix(mesh):
    """setup()/update() must carry the functional surface's full feature
    matrix: zero_stage=3 (flat sharded master params, target()
    materializes), n_accum, has_aux, loss_scale — trajectories equal the
    plain functional path."""
    comm = create_communicator("xla_ici", mesh=mesh)
    params, batch = make_problem()

    def aux_loss(p, b):
        l = loss_fn(p, b)
        return l, {"l2": sum(jnp.sum(x * x) for x in jax.tree.leaves(p))}

    # Oracle: plain replicated functional path, same inner optimizer.
    ref = create_multi_node_optimizer(optax.sgd(0.1), comm)
    rstate = ref.init(params)
    rstep = ref.make_train_step(loss_fn, donate=False)
    rp = params
    for _ in range(3):
        rp, rstate, _ = rstep(rp, rstate, batch)

    # Imperative ZeRO-3 + n_accum + has_aux + loss_scale.
    opt = create_multi_node_optimizer(optax.sgd(0.1), comm, zero_stage=3)
    opt.setup(
        params, aux_loss, n_accum=2, has_aux=True, loss_scale=128.0
    )
    for _ in range(3):
        loss, aux = opt.update(batch)
        assert np.isfinite(float(loss))
        assert aux["l2"].shape[0] == 2  # stacked over n_accum
    assert opt.t == 3
    tgt = opt.target
    for k in params:
        np.testing.assert_allclose(
            np.asarray(tgt[k]), np.asarray(rp[k]), rtol=1e-4, atol=1e-5
        )


@pytest.mark.parametrize("zero_stage", [0, 1, 3])
def test_steps_expose_jit_aot_surface(devices8, zero_stage):
    """``chip_smoke.py`` lowers the step for its compiled text and the
    collective linter reads donation off ``trace``: every built step
    carries jit's ``lower``/``trace``/``eval_shape`` through its
    wrappers."""
    comm = create_communicator("xla_ici")
    opt = create_multi_node_optimizer(
        optax.sgd(0.1), comm, zero_stage=zero_stage)
    params = {"w": jnp.ones((16, 4))}
    state = opt.init(params)
    if zero_stage == 3:
        params = opt.shard_params(params)
    batch = jnp.ones((8, 16))
    stats = {"n": jnp.zeros(())}
    plain = opt.make_train_step(
        lambda p, b: jnp.mean((b @ p["w"]) ** 2), donate=True)
    with_state = opt.make_train_step_with_state(
        lambda p, s, b: (jnp.mean((b @ p["w"]) ** 2), s), donate=True)
    for step, args, donated in (
        (plain, (params, state, batch), (0, 1)),
        (with_state, (params, state, stats, batch), (0, 1, 2)),
    ):
        assert step.trace(*args).donate_argnums == donated
        assert step.eval_shape(*args)[-1].shape == ()
        hlo = step.lower(*args).compile().as_text()
        assert "all-reduce" in hlo or "reduce-scatter" in hlo

