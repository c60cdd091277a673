"""Operations and bytes a ``nemotron_h`` training step needs, from shapes
alone and, for the held experts, from how many (token, choice) pairs were
routed to them (the yardstick's own arithmetic, beside ``flops.py``, whose
peaks table and roofline rule it uses, and ``flops_hybrid.py``, whose
count of the state-space recurrence it shares).

Model FLOPs follow ``flops.lm_train_flops_per_token``'s convention — 6 a
parameter a token for every parameter all tokens pass (the routers among
them), causal attention in the attention layers only, the recurrence as
``flops_hybrid.ssd_flops`` counts it, nothing recomputed — and, for a held
expert's two matrices, 6 a parameter a PAIR routed to it: with 8 of 128
experts held and 6 chosen a token, 6 x 8/128 = 0.375 pairs a token in
expectation.  ``step.mfu`` takes the expectation, so that it does not move
with a seed's routing; the grouped matmul's roofline share takes the pairs
the traced steps themselves routed to the held experts (the step hands
its routers' choice back), so that it moves with the kernels and not with
the routing.
"""

from chipbench import flops, flops_hybrid, weights_nemotron


def expert_layers(z):
    return sum(1 for k in z["kinds"] if k == "experts")


def expected_held_pairs(config, mix):
    """Pairs a step an expert layer routes to the held experts when every
    expert is as likely as another."""
    z = weights_nemotron.sizes(config)
    tokens = int(mix["global_batch"]) * int(mix["seq_len"])
    return tokens * z["top_k"] * z["held"] / z["experts"]


def gmm_flops(held_pairs, z):
    """Needed FLOPs of the held experts' two matrices over ``held_pairs``
    rows (one number a layer): forward 2 a weight a row, backward twice
    that."""
    return sum(12.0 * z["d"] * z["d_expert"] * p for p in held_pairs)


def gmm_bytes(held_pairs, z, itemsize=2):
    """Least HBM traffic of the same: forward reads the rows and the
    weights (compute type) and writes the results; backward reads rows,
    incoming gradients and weights and writes the rows' gradients and the
    float32 weight gradients.  The hidden activations between the two
    matrices, which a fused form would not write, are left out."""
    weights = 2 * z["held"] * z["d"] * z["d_expert"]
    return sum(5.0 * p * z["d"] * itemsize + weights * (2 * itemsize + 4)
               for p in held_pairs)


def train_flops_per_step(config, mix):
    """Model FLOPs of one step of the cell, nothing recomputed, the held
    experts at their expected load."""
    z = weights_nemotron.sizes(config)
    B, S = int(mix["global_batch"]), int(mix["seq_len"])
    n_experts = expert_layers(z)
    routed = n_experts * 2 * z["held"] * z["d"] * z["d_expert"]
    attention_layers = sum(1 for k in z["kinds"] if k == "attention")
    return (6.0 * (weights_nemotron.n_params(config) - routed) * B * S
            + gmm_flops([expected_held_pairs(config, mix)] * n_experts, z)
            + flops.causal_attention_flops(
                B, S, z["heads"], z["d_head"], attention_layers)
            + flops_hybrid.ssd_flops(B, S, z))


def gmm_roofline_seconds(config, mix, device_kind, held_pairs=None):
    z = weights_nemotron.sizes(config)
    if held_pairs is None:
        held_pairs = [expected_held_pairs(config, mix)] * expert_layers(z)
    return flops.roofline_seconds(
        gmm_flops(held_pairs, z), gmm_bytes(held_pairs, z),
        flops.peaks(device_kind))


def ssd_roofline_seconds(config, mix, device_kind):
    """What the recurrence needs, as ``flops_hybrid`` counts it, at this
    family's sizes."""
    z = weights_nemotron.sizes(config)
    B, S = int(mix["global_batch"]), int(mix["seq_len"])
    return flops.roofline_seconds(
        flops_hybrid.ssd_flops(B, S, z), flops_hybrid.ssd_bytes(B, S, z),
        flops.peaks(device_kind))


def flash_bytes(batch, seq_len, z, n_layers, itemsize=2):
    """Least HBM traffic of grouped-query flash attention forward +
    backward: ``flops.causal_attention_bytes``' twelve passes, six of them
    (K, V forward; K, V, dK, dV backward) at the key/value heads' width."""
    return (6.0 * (z["heads"] + z["kv_heads"]) * z["d_head"] * itemsize
            * batch * seq_len * n_layers)


def flash_roofline_seconds(config, mix, device_kind):
    z = weights_nemotron.sizes(config)
    B, S = int(mix["global_batch"]), int(mix["seq_len"])
    n = sum(1 for k in z["kinds"] if k == "attention")
    return flops.roofline_seconds(
        flops.causal_attention_flops(B, S, z["heads"], z["d_head"], n),
        flash_bytes(B, S, z, n), flops.peaks(device_kind))
