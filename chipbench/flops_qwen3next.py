"""Operations and bytes a ``qwen3_next`` training step needs, from shapes
alone and, for the held experts, from how many (token, choice) pairs were
routed to them (the yardstick's own arithmetic, beside ``flops.py``, whose
peaks table and roofline rule it uses, as ``flops_nemotron.py`` is for its
family).  Nothing here imports the program.

Model FLOPs: 6 a parameter a token for every parameter of a MATRIX all
tokens pass (the mixers' projections, the routers, the shared experts,
the head; the convolution's taps and the vectors ride along) — the
embedding table is a lookup and is NOT counted, unlike the older files'
``6 n_params`` —, causal attention in the attention row at its 16 heads
of 256, the gated delta rule as :func:`gdn_scan_flops` counts it, nothing
recomputed; and, for a held expert's three matrices, 6 a parameter a PAIR
routed to it: with 32 of 512 experts held and 10 chosen a token, 0.625
pairs a token in expectation.  ``step.mfu`` takes the expectation;
``kernel.moe_gmm_roofline`` takes the pairs the traced steps themselves routed
to the held experts.

The gated delta rule is counted in its CHUNKED form at :data:`CHUNK`
tokens a chunk — the published kernels' own chunk, a constant of this
file and not read off the program, so that another chunk, another solve
or a kernel in the program moves ``gdn-scan``'s time and not what it is
held against.
"""

from chipbench import flops, weights_qwen3next

#: Tokens a chunk of the chunked rule whose work is counted.
CHUNK = 64


def gdn_layers(z):
    return sum(1 for k in z["kinds"] if k == "gdn")


def attention_layers(z):
    return sum(1 for k in z["kinds"] if k == "attention")


def gdn_scan_flops_per_chunk_head(d_k, d_v, chunk=CHUNK):
    """Forward FLOPs of one chunk of one value head, every product as a
    full matrix product at 2 a multiply-add: ``k k^T`` and ``q k^T``
    (2 C^2 d_k each), ``T`` applied to ``beta e^G k`` and ``beta v``
    (2 C^2 (d_k + d_v)), ``tril(q k^T ..) v_new`` (2 C^2 d_v), ``W S``,
    ``(q e^G) S`` and ``(k e^..)^T v_new`` (2 C d_k d_v each).  The
    triangular solve itself (C^3 / 3) and the elementwise decays are left
    out."""
    C = chunk
    return (4.0 * C * C * d_k + 2.0 * C * C * (d_k + d_v)
            + 2.0 * C * C * d_v + 6.0 * C * d_k * d_v)


def gdn_scan_flops(batch, seq_len, z):
    """Needed FLOPs of the chunked gated delta rule, forward + backward
    (twice the forward), for one step."""
    chunks = batch * seq_len / CHUNK
    return (3.0 * gdn_scan_flops_per_chunk_head(z["d_k"], z["d_v"])
            * chunks * z["v_heads"] * gdn_layers(z))


def gdn_scan_bytes(batch, seq_len, z, itemsize=2):
    """Least HBM traffic of the same: forward reads q, k (at the key
    heads' width), v (compute type), g and beta (float32) and writes o;
    backward reads those and do and writes dq, dk, dv, dg and dbeta.
    The chunk states and every intermediate of the chunked form, which a
    fused form would not write, are left out."""
    qk = 2 * z["key_dim"] * itemsize
    v = z["value_dim"] * itemsize
    gb = 2 * z["v_heads"] * 4
    per_token = (qk + 2 * v + gb) + (2 * qk + 3 * v + 2 * gb)
    return float(per_token) * batch * seq_len * gdn_layers(z)


def gdn_scan_roofline_seconds(config, mix, device_kind):
    z = weights_qwen3next.sizes(config)
    B, S = int(mix["global_batch"]), int(mix["seq_len"])
    return flops.roofline_seconds(
        gdn_scan_flops(B, S, z), gdn_scan_bytes(B, S, z),
        flops.peaks(device_kind))


def expected_held_pairs(config, mix):
    """Pairs a step a layer routes to the held experts when every expert
    is as likely as another."""
    z = weights_qwen3next.sizes(config)
    tokens = int(mix["global_batch"]) * int(mix["seq_len"])
    return tokens * z["top_k"] * z["held"] / z["experts"]


def gmm_flops(held_pairs, z):
    """Needed FLOPs of the held experts' three matrices over
    ``held_pairs`` rows (one number a layer): forward 2 a weight a row,
    backward twice that — 18 d f a pair."""
    return sum(18.0 * z["d"] * z["d_expert"] * p for p in held_pairs)


def gmm_bytes(held_pairs, z, itemsize=2):
    """Least HBM traffic of the same, as ``flops_zaya.gmm_bytes`` counts
    it: forward reads the rows and the weights (compute type) and writes
    the results; backward reads rows, incoming gradients and weights and
    writes the rows' gradients and the float32 weight gradients."""
    weights = 3 * z["held"] * z["d"] * z["d_expert"]
    return sum(5.0 * p * z["d"] * itemsize + weights * (2 * itemsize + 4)
               for p in held_pairs)


def gmm_roofline_seconds(config, mix, device_kind, held_pairs=None):
    z = weights_qwen3next.sizes(config)
    if held_pairs is None:
        held_pairs = [expected_held_pairs(config, mix)] * z["layers"]
    return flops.roofline_seconds(
        gmm_flops(held_pairs, z), gmm_bytes(held_pairs, z),
        flops.peaks(device_kind))


def flash_bytes(batch, seq_len, z, n_layers, itemsize=2):
    """Least HBM traffic of grouped-query flash attention forward +
    backward: ``flops.causal_attention_bytes``' twelve passes, six of them
    (K, V forward; K, V, dK, dV backward) at the key/value heads' width."""
    return (6.0 * (z["heads"] + z["kv_heads"]) * z["d_head"] * itemsize
            * batch * seq_len * n_layers)


def flash_roofline_seconds(config, mix, device_kind):
    z = weights_qwen3next.sizes(config)
    B, S = int(mix["global_batch"]), int(mix["seq_len"])
    n = attention_layers(z)
    return flops.roofline_seconds(
        flops.causal_attention_flops(B, S, z["heads"], z["d_head"], n),
        flash_bytes(B, S, z, n), flops.peaks(device_kind))


def matrix_params(config):
    """Parameters every token multiplies: all of them but the held
    experts' stacks (counted by the pair) and the embedding table (a
    lookup)."""
    z = weights_qwen3next.sizes(config)
    routed = z["layers"] * 3 * z["held"] * z["d"] * z["d_expert"]
    return weights_qwen3next.n_params(config) - routed - z["vocab"] * z["d"]


def train_flops_per_step(config, mix):
    """Model FLOPs of one step of the cell, nothing recomputed, the held
    experts at their expected load."""
    z = weights_qwen3next.sizes(config)
    B, S = int(mix["global_batch"]), int(mix["seq_len"])
    return (6.0 * matrix_params(config) * B * S
            + gmm_flops([expected_held_pairs(config, mix)] * z["layers"], z)
            + flops.causal_attention_flops(
                B, S, z["heads"], z["d_head"], attention_layers(z))
            + gdn_scan_flops(B, S, z))
