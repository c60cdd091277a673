"""Operations and bytes a ``bailing_hybrid`` (Ling-3.0) training step
needs, from shapes alone and, for the held experts, from how many (token,
choice) pairs were routed to them (the yardstick's own arithmetic, beside
``flops.py``, whose peaks table and roofline rule it uses, as
``flops_qwen3next.py`` is for its family).  Nothing here imports the
program.

Model FLOPs: 6 a parameter a token for every parameter of a MATRIX all
tokens pass (the mixers' projections, the latent's two matrices, the
dense FFNs, the routers, the shared experts, the head; the convolution's
taps and the vectors ride along) — the embedding table is a lookup and is
NOT counted —, causal attention in the latent row at its 32 heads scoring
over 192 and summing values of 128, the delta rule under its per-channel
decay as :func:`kda_scan_flops` counts it, nothing recomputed; and, for a
held expert's three matrices, 6 a parameter a PAIR routed to it: with 8 of
512 experts held and 8 chosen a token, 0.125 pairs a token in expectation.
``step.mfu`` takes the expectation; ``kernel.moe_gmm_roofline`` takes the pairs
the traced steps themselves routed to the held experts.

The rule is counted in its CHUNKED form at :data:`CHUNK` tokens a chunk —
the published kernels' own chunk, a constant of this file and not read
off the program, so that another chunk, another solve, the sub-blocks'
width or a kernel in the program moves ``kda-scan``'s time and not what
it is held against.  The split of a decay over the two operands of ``k
k^T`` and ``q k^T`` is elementwise work and adds no product.
"""

from chipbench import flops, weights_ling3
from chipbench.flops_qwen3next import gmm_bytes, gmm_flops

#: Tokens a chunk of the chunked rule whose work is counted.
CHUNK = 64


def sizes(config):
    kinds = weights_ling3.kinds(config)
    return dict(
        d=config["hidden_size"], vocab=config["vocab_size"],
        heads=config["num_attention_heads"], d_k=config["head_dim"],
        d_v=config["head_dim"],
        d_qk=config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
        d_value=config["v_head_dim"],
        kda_layers=sum(1 for m, _ in kinds if m == "kda"),
        mla_layers=sum(1 for m, _ in kinds if m == "mla"),
        sparse_layers=sum(1 for _, f in kinds if f == "sparse"),
        experts=config["num_experts_published"],
        held=config["num_experts"], top_k=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"])


def kda_scan_flops_per_chunk_head(d_k, d_v, chunk=CHUNK):
    """Forward FLOPs of one chunk of one head, every product as a full
    matrix product at 2 a multiply-add: ``k k^T`` and ``q k^T`` under
    their decays (2 C^2 d_k each), ``T`` applied to ``beta e^G k`` and
    ``beta v`` (2 C^2 (d_k + d_v)), ``tril(..) v_new`` (2 C^2 d_v), ``W
    S``, ``(q e^G) S`` and ``(k e^..)^T v_new`` (2 C d_k d_v each).  The
    triangular solve itself (C^3 / 3) and the elementwise decays are left
    out.  By hand at C = 64, d_k = d_v = 128: 2,097,152 + 2,097,152 +
    1,048,576 + 6,291,456 = 11,534,336."""
    C = chunk
    return (4.0 * C * C * d_k + 2.0 * C * C * (d_k + d_v)
            + 2.0 * C * C * d_v + 6.0 * C * d_k * d_v)


def kda_scan_flops(batch, seq_len, z):
    """Needed FLOPs of the chunked rule, forward + backward (twice the
    forward), for one step."""
    chunks = batch * seq_len / CHUNK
    return (3.0 * kda_scan_flops_per_chunk_head(z["d_k"], z["d_v"])
            * chunks * z["heads"] * z["kda_layers"])


def kda_scan_bytes(batch, seq_len, z, itemsize=2):
    """Least HBM traffic of the same: forward reads q, k, v (compute
    type), g (float32, one number a KEY CHANNEL) and beta (float32) and
    writes o; backward reads those and do and writes dq, dk, dv, dg and
    dbeta.  The chunk states and every intermediate of the chunked form,
    which a fused form would not write, are left out."""
    qk = 2 * z["d_k"] * itemsize
    v = z["d_v"] * itemsize
    g, b = z["d_k"] * 4, 4
    per_token_head = (qk + 2 * v + g + b) + (2 * qk + 3 * v + 2 * g + 2 * b)
    return (float(per_token_head) * z["heads"] * batch * seq_len
            * z["kda_layers"])


def kda_scan_roofline_seconds(config, mix, device_kind):
    z = sizes(config)
    B, S = int(mix["global_batch"]), int(mix["seq_len"])
    return flops.roofline_seconds(
        kda_scan_flops(B, S, z), kda_scan_bytes(B, S, z),
        flops.peaks(device_kind))


def flash_flops(batch, seq_len, z):
    """Needed FLOPs of causal attention forward + backward whose scores
    are ``d_qk`` wide and whose values ``d_value``: a (query, key) pair
    costs 2 d_qk (``q k^T``) + 2 d_value (``p v``) forward and twice that
    backward; the triangle's pairs, the diagonal among them."""
    pairs = seq_len * (seq_len + 1) / 2.0
    return (6.0 * pairs * (z["d_qk"] + z["d_value"]) * z["heads"] * batch
            * z["mla_layers"])


def flash_bytes(batch, seq_len, z, itemsize=2):
    """Least HBM traffic of the same: twelve passes over a (B, S, H, .)
    tensor, six at the scores' width (Q, K forward; Q, K, dQ, dK
    backward) and six at the values' (V, O forward; V, O, dO, dV
    backward)."""
    return (6.0 * (z["d_qk"] + z["d_value"]) * z["heads"] * itemsize
            * batch * seq_len * z["mla_layers"])


def flash_roofline_seconds(config, mix, device_kind):
    z = sizes(config)
    B, S = int(mix["global_batch"]), int(mix["seq_len"])
    return flops.roofline_seconds(
        flash_flops(B, S, z), flash_bytes(B, S, z), flops.peaks(device_kind))


def expected_held_pairs(config, mix):
    """Pairs a step a layer routes to the held experts when every expert
    is as likely as another."""
    z = sizes(config)
    tokens = int(mix["global_batch"]) * int(mix["seq_len"])
    return tokens * z["top_k"] * z["held"] / z["experts"]


def gmm_roofline_seconds(config, mix, device_kind, held_pairs=None):
    z = sizes(config)
    if held_pairs is None:
        held_pairs = [expected_held_pairs(config, mix)] * z["sparse_layers"]
    return flops.roofline_seconds(
        gmm_flops(held_pairs, z), gmm_bytes(held_pairs, z),
        flops.peaks(device_kind))


def matrix_params(config):
    """Parameters every token multiplies: all of them but the held
    experts' stacks (counted by the pair) and the embedding table (a
    lookup)."""
    z = sizes(config)
    routed = z["sparse_layers"] * 3 * z["held"] * z["d"] * z["d_expert"]
    return weights_ling3.n_params(config) - routed - z["vocab"] * z["d"]


def train_flops_per_step(config, mix):
    """Model FLOPs of one step of the cell, nothing recomputed, the held
    experts at their expected load."""
    z = sizes(config)
    B, S = int(mix["global_batch"]), int(mix["seq_len"])
    return (6.0 * matrix_params(config) * B * S
            + gmm_flops([expected_held_pairs(config, mix)]
                        * z["sparse_layers"], z)
            + flash_flops(B, S, z) + kda_scan_flops(B, S, z))
