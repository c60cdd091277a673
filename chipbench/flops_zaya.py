"""Operations and bytes a ``zaya`` training step needs, from shapes alone
and, for the held experts, from how many tokens were routed to them (the
yardstick's own arithmetic, beside ``flops.py``, whose peaks table and
roofline rule it uses, as ``flops_nemotron.py`` is for its family).

Model FLOPs follow ``flops.lm_train_flops_per_token``'s convention — 6 a
parameter a token for every parameter all tokens pass (the CCA
projections, the grouped convolution's matrices and the routers among
them; the convolutions' taps and the vectors ride along), causal
attention at the LATENT's width (``num_attention_heads x head_dim``, not
the model's), nothing recomputed — and, for a held expert's three
matrices, 6 a parameter a PAIR routed to it: with 8 of 16 experts held
and one chosen a token, 0.5 pairs a token in expectation.  ``step.mfu``
takes the expectation, so that it does not move with a seed's routing;
the grouped matmul's roofline share takes the pairs the traced steps
themselves routed to the held experts (the step hands its routers' choice
back), so that it moves with the kernels and not with the routing.
"""

from chipbench import flops, weights_zaya


def expected_held_pairs(config, mix):
    """Pairs a step a layer routes to the held experts when every expert
    is as likely as another."""
    z = weights_zaya.sizes(config)
    tokens = int(mix["global_batch"]) * int(mix["seq_len"])
    return tokens * z["top_k"] * z["held"] / z["experts"]


def gmm_flops(held_pairs, z):
    """Needed FLOPs of the held experts' three matrices over
    ``held_pairs`` rows (one number a layer): forward 2 a weight a row,
    backward twice that — 18 d f a pair."""
    return sum(18.0 * z["d"] * z["d_expert"] * p for p in held_pairs)


def gmm_bytes(held_pairs, z, itemsize=2):
    """Least HBM traffic of the same: forward reads the rows and the
    weights (compute type) and writes the results; backward reads rows,
    incoming gradients and weights and writes the rows' gradients and the
    float32 weight gradients.  The gate's, the up-projection's and the
    hidden activations between the matrices, which a fused form would not
    write, are left out."""
    weights = 3 * z["held"] * z["d"] * z["d_expert"]
    return sum(5.0 * p * z["d"] * itemsize + weights * (2 * itemsize + 4)
               for p in held_pairs)


def train_flops_per_step(config, mix):
    """Model FLOPs of one step of the cell, nothing recomputed, the held
    experts at their expected load."""
    z = weights_zaya.sizes(config)
    B, S = int(mix["global_batch"]), int(mix["seq_len"])
    routed = z["layers"] * 3 * z["held"] * z["d"] * z["d_expert"]
    return (6.0 * (weights_zaya.n_params(config) - routed) * B * S
            + gmm_flops([expected_held_pairs(config, mix)] * z["layers"], z)
            + flops.causal_attention_flops(
                B, S, z["heads"], z["d_head"], z["layers"]))


def gmm_roofline_seconds(config, mix, device_kind, held_pairs=None):
    z = weights_zaya.sizes(config)
    if held_pairs is None:
        held_pairs = [expected_held_pairs(config, mix)] * z["layers"]
    return flops.roofline_seconds(
        gmm_flops(held_pairs, z), gmm_bytes(held_pairs, z),
        flops.peaks(device_kind))


def flash_bytes(batch, seq_len, z, itemsize=2):
    """Least HBM traffic of grouped-query flash attention forward +
    backward in the latent: ``flops.causal_attention_bytes``' twelve
    passes, six of them (K, V forward; K, V, dK, dV backward) at the
    key/value heads' width."""
    return (6.0 * (z["heads"] + z["kv_heads"]) * z["d_head"] * itemsize
            * batch * seq_len * z["layers"])


def flash_roofline_seconds(config, mix, device_kind):
    z = weights_zaya.sizes(config)
    B, S = int(mix["global_batch"]), int(mix["seq_len"])
    return flops.roofline_seconds(
        flops.causal_attention_flops(
            B, S, z["heads"], z["d_head"], z["layers"]),
        flash_bytes(B, S, z), flops.peaks(device_kind))
