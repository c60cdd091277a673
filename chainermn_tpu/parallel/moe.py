"""Expert parallelism with a capacity factor — mixture-of-experts with
all-to-all token routing, for layouts where every device sends a FIXED
number of slots to every expert.

Net-new (SURVEY §2.5: "EP/MoE: reference has nothing").  One expert (or
an equal group of experts) lives on each device of a mesh axis; tokens
are routed GShard-style, top-1 or top-2 by softmax, into an ``(E, C, T)``
one-hot dispatch of ``capacity`` slots an expert, sent to the experts'
devices with ``lax.all_to_all``, run there batched on the MXU, and routed
back by a second all-to-all — built on the same differentiable
``alltoall`` primitive the reference exposed as a collective Function
(REF:chainermn/functions/collective_communication.py) without ever using
it this way.

Capacity-based dispatch keeps shapes static for XLA: each device sends
exactly ``capacity`` token slots to every expert (padded with zeros,
weighted 0), so the program is retrace-free regardless of routing skew —
and a token whose expert is full is DROPPED.  That, and a dispatch tensor
that grows with experts x capacity x tokens, is why this path is for few
experts and small top-k (its tests; no benchmark cell).  A published
top-k-of-many router that drops nothing — sigmoid scores, 6 of 128, a
rank that holds 8 of them — is ``parallel/moe_dropless.py``: pairs sorted
by expert and grouped matmuls over the experts held, no capacity, and so
far no exchange between ranks (this module's all-to-all is what it would
borrow).
"""

from __future__ import annotations

import warnings
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax


def _axis_size(axis_name) -> int:
    """Static mapped-axis size."""
    return lax.axis_size(axis_name)


def topk_route(gate_logits: jax.Array, n_experts: int, capacity: int,
               k: int = 1):
    """Top-k routing with per-(device, expert) capacity (GShard-style).

    gate_logits: (T, E).  Returns (dispatch, combine):
      dispatch: (E, C, T) one-hot dispatch mask (token t fills slot c of
                expert e), zeros for dropped/padded slots;
      combine:  (E, C, T) dispatch × gate weight (the weight used when
                summing expert outputs back per token).

    For ``k > 1`` each token goes to its k highest-probability experts with
    gates renormalized over the chosen set; first choices claim capacity
    slots before second choices (choice-major priority, as in GShard).
    """
    T, E = gate_logits.shape
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)

    onehots, gates = [], []
    remaining = probs
    for _ in range(k):
        idx = jnp.argmax(remaining, axis=-1)
        oh = jax.nn.one_hot(idx, E, dtype=jnp.float32)       # (T, E)
        gate = jnp.sum(remaining * oh, axis=-1)              # raw prob
        # Degenerate choice guard: if the remaining mass is exactly zero
        # (softmax collapsed onto earlier choices), argmax returns index 0
        # spuriously — drop the choice instead of burning a capacity slot.
        oh = oh * (gate > 0)[:, None]
        gates.append(gate)
        onehots.append(oh)
        remaining = remaining * (1.0 - oh)
    if k > 1:
        # GShard renormalizes over the chosen set; for k=1 the Switch
        # combine weight IS the router probability (renormalizing would
        # pin it to ~1 and starve the router of main-loss gradient).
        denom = sum(gates) + 1e-9
        gates = [g / denom for g in gates]

    dispatch = jnp.zeros((E, capacity, T), jnp.float32)
    combine = jnp.zeros((E, capacity, T), jnp.float32)
    claimed = jnp.zeros((E,), jnp.float32)   # slots used by earlier choices
    for oh, gate in zip(onehots, gates):
        # Position within the expert queue: within-choice arrival order,
        # offset by slots earlier choices already claimed.
        pos = (jnp.cumsum(oh, axis=0) - 1.0 + claimed[None, :]) * oh
        pos = pos - (1.0 - oh)                               # -1 off-expert
        kept = (pos >= 0) & (pos < capacity)
        slot = jnp.where(kept, pos, 0).astype(jnp.int32)     # (T, E)
        slot_onehot = (
            jax.nn.one_hot(slot, capacity, dtype=jnp.float32)
            * kept[..., None]
        )                                                    # (T, E, C)
        d = jnp.einsum("te,tec->ect", oh, slot_onehot)
        dispatch = dispatch + d
        combine = combine + d * gate[None, None, :]
        claimed = claimed + jnp.sum(oh, axis=0)
    return dispatch, combine


def top1_route(gate_logits: jax.Array, n_experts: int, capacity: int):
    """Top-1 routing (Switch-style) — see :func:`topk_route`."""
    return topk_route(gate_logits, n_experts, capacity, k=1)


def load_balancing_loss(gate_logits: jax.Array, n_experts: int):
    """Switch-Transformer auxiliary load-balancing loss.

    ``E * Σ_e f_e · P_e`` where ``f_e`` is the fraction of tokens whose
    top-1 expert is ``e`` and ``P_e`` the mean router probability of ``e``;
    equals 1.0 under perfectly uniform routing, grows as routing collapses.
    Add ``aux_weight * load_balancing_loss(...)`` (typical weight 1e-2) to
    the training loss.
    """
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    top1 = jax.nn.one_hot(jnp.argmax(probs, -1), n_experts, dtype=jnp.float32)
    f = jnp.mean(top1, axis=0)
    P = jnp.mean(probs, axis=0)
    return n_experts * jnp.sum(f * P)


def moe_layer(
    x: jax.Array,
    gate_w: jax.Array,
    expert_fn: Callable,
    expert_params,
    axis_name: str,
    capacity_factor: float = 2.0,
    k: int = 1,
    return_aux: bool | str = False,
    experts_per_device: int = 1,
):
    """Expert-parallel MoE FFN; call inside ``shard_map`` over ``axis_name``.

    ``x``: (T_local, D) this device's tokens.  ``gate_w``: (D, E) router
    weights (replicated), with ``E = axis_size * experts_per_device``.
    ``expert_params``: THIS device's experts' parameters — for
    ``experts_per_device == 1`` the bare pytree (back-compat); for more,
    every leaf leads with an ``(experts_per_device, ...)`` axis and the
    experts run under ``vmap`` (device ``d`` owns global experts
    ``d*epd .. (d+1)*epd - 1`` — device-major layout, so the all-to-all's
    leading-axis split IS the expert→device map).
    ``expert_fn(params, tokens) -> tokens`` is one expert's computation.
    ``k``: experts per token (1 = Switch, 2 = GShard top-2).
    ``return_aux``: also return an aux dict for this device's tokens:

    * ``"load_balance_loss"`` — the Switch auxiliary loss (add to the
      training loss, typical weight 1e-2);
    * ``"dropped_fraction"`` — fraction of the ``k*T`` (token, choice)
      routings NOT granted a capacity slot (passed through as zeros);
      the router-health gauge capacity_factor should be tuned against.

    .. note:: **Changed contract.** ``return_aux=True`` used to return
       ``(y, scalar_load_balance_loss)``; it now returns ``(y, dict)``
       as documented above.  Callers still expecting the bare scalar can
       pass ``return_aux="scalar"`` for one release — it returns the old
       ``(y, load_balance_loss)`` pair and emits a
       :class:`DeprecationWarning`.  The shim will be removed; switch to
       ``return_aux=True`` and read ``aux["load_balance_loss"]``.

    Returns (T_local, D) with each token replaced by its experts' outputs
    weighted by the gates (dropped-by-capacity tokens pass through as
    zeros, as in Switch)."""
    n = _axis_size(axis_name)
    epd = experts_per_device
    if epd < 1:
        raise ValueError(f"experts_per_device must be >= 1, got {epd}")
    E = n * epd
    T, D = x.shape
    if gate_w.shape[1] != E:
        raise ValueError(
            f"gate_w routes to {gate_w.shape[1]} experts but the layout "
            f"is {n} devices x {epd} experts/device = {E}"
        )
    capacity = max(1, int(capacity_factor * k * T / E))

    gate_logits = x @ gate_w                                # (T, E)
    dispatch, combine = topk_route(gate_logits, E, capacity, k=k)

    # Gather each expert's slots from local tokens: (E, C, D).
    expert_in = jnp.einsum("ect,td->ecd", dispatch, x.astype(jnp.float32))
    # All-to-all: the device-major expert axis splits into n chunks of
    # epd, so device d ends up with ITS experts' slots from every source:
    # (E, C, D) -> (n*epd, C, D) ordered (source, local expert).
    expert_in = lax.all_to_all(expert_in, axis_name, split_axis=0, concat_axis=0, tiled=True)
    if epd == 1:
        # Run the local expert on all (n*C) slots.
        flat = expert_in.reshape(n * capacity, D).astype(x.dtype)
        out = expert_fn(expert_params, flat).astype(jnp.float32)
        out = out.reshape(n, capacity, D)
    else:
        # (source, local expert, C, D) -> per-expert batches, vmapped.
        grp = (
            expert_in.reshape(n, epd, capacity, D)
            .transpose(1, 0, 2, 3)
            .reshape(epd, n * capacity, D)
            .astype(x.dtype)
        )
        out = jax.vmap(expert_fn)(expert_params, grp).astype(jnp.float32)
        out = (
            out.reshape(epd, n, capacity, D)
            .transpose(1, 0, 2, 3)
            .reshape(E, capacity, D)
        )
    # Route back: leading axis returns to expert-major layout per source.
    out = lax.all_to_all(
        out.reshape(E, capacity, D), axis_name,
        split_axis=0, concat_axis=0, tiled=True,
    )
    # Combine: token t = sum over (e, c) of combine[e,c,t] * out[e,c,:].
    y = jnp.einsum("ect,ecd->td", combine, out).astype(x.dtype)
    if return_aux:
        aux = {
            "load_balance_loss": load_balancing_loss(gate_logits, E),
            # dispatch holds exactly one 1 per GRANTED (token, choice);
            # k*T is every routing the tokens asked for (zero-gate
            # degenerate choices count as dropped — they carry no output
            # either way).
            "dropped_fraction": 1.0 - jnp.sum(dispatch) / (k * T),
        }
        if return_aux == "scalar":
            # One-release back-compat shim for the (y, scalar) contract.
            warnings.warn(
                "moe_layer(return_aux='scalar') is deprecated: "
                "return_aux=True now returns (y, aux_dict); read "
                "aux['load_balance_loss'] instead.  The 'scalar' shim "
                "will be removed next release.",
                DeprecationWarning,
                stacklevel=2,
            )
            return y, aux["load_balance_loss"]
        return y, aux
    return y


def dense_moe_oracle(x, gate_w, expert_fn, all_expert_params,
                     capacity_factor=2.0, k=1):
    """Single-device oracle: same routing math with all experts local."""
    E = gate_w.shape[1]
    T, D = x.shape
    capacity = max(1, int(capacity_factor * k * T / E))
    dispatch, combine = topk_route(x @ gate_w, E, capacity, k=k)
    expert_in = jnp.einsum("ect,td->ecd", dispatch, x.astype(jnp.float32))
    outs = []
    for e in range(E):
        params_e = jax.tree.map(lambda p: p[e], all_expert_params)
        outs.append(expert_fn(params_e, expert_in[e].astype(x.dtype)).astype(jnp.float32))
    out = jnp.stack(outs)
    return jnp.einsum("ect,ecd->td", combine, out).astype(x.dtype)
