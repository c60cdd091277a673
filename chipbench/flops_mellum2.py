"""Operations and bytes a ``mellum`` training step needs, from shapes
alone and, for the held experts, from how many (token, choice) pairs were
routed to them (the yardstick's own arithmetic, beside ``flops.py``, whose
peaks table and roofline rule it uses, as ``flops_qwen3next.py`` is for
its family; the held experts' and the flash kernels' byte counts are that
file's, the same forms).  Nothing here imports the program.

Model FLOPs: 6 a parameter a token for every parameter of a MATRIX all
tokens pass (the projections, the routers, the head; the vectors ride
along; the embedding table is a lookup and is not counted), 6 a parameter
a PAIR routed to a held expert (8 of 64 held and 8 chosen a token: one
pair a token in expectation), and attention by the (query, key) pairs a
row ATTENDS: the band's ``W (W + 1) / 2 + (S - W) W`` in a
``sliding_attention`` layer, the triangle's ``S (S + 1) / 2`` in a
``full_attention`` one — 4 a pair a head dimension forward, twice that
backward — whatever tiles a kernel visits to cover them.  Nothing
recomputed.
"""

from chipbench import flops, weights_mellum2
from chipbench.flops_qwen3next import flash_bytes, gmm_bytes, gmm_flops

KINDS = ("sliding_attention", "full_attention")


def attended_pairs(seq_len, reach=None):
    """(query, key) pairs of one row of ``seq_len`` tokens with ``0 <=
    q_pos - k_pos < reach`` (None: the whole triangle)."""
    w = seq_len if reach is None else min(int(reach), seq_len)
    return w * (w + 1) // 2 + (seq_len - w) * w


def layers_of(z, kind):
    return sum(1 for k in z["kinds"] if k == kind)


def reach(z, kind):
    return z["window"] if kind == "sliding_attention" else None


def flash_flops(batch, seq_len, z, kind):
    """Needed FLOPs of attention forward + backward in the layers of
    ``kind`` for one step: 12 a pair a head dimension."""
    return (12.0 * attended_pairs(seq_len, reach(z, kind)) * batch
            * z["heads"] * z["d_head"] * layers_of(z, kind))


def flash_roofline_seconds(config, mix, device_kind, kind):
    """The least time of the flash kernels of the layers of ``kind``:
    the pairs' FLOPs and ``flops_qwen3next.flash_bytes``' twelve passes
    (a window moves no fewer bytes: every query, key and value is read)."""
    z = weights_mellum2.sizes(config)
    B, S = int(mix["global_batch"]), int(mix["seq_len"])
    return flops.roofline_seconds(
        flash_flops(B, S, z, kind),
        flash_bytes(B, S, z, layers_of(z, kind)), flops.peaks(device_kind))


def expected_held_pairs(config, mix):
    """Pairs a step a layer routes to the held experts when every expert
    is as likely as another."""
    z = weights_mellum2.sizes(config)
    tokens = int(mix["global_batch"]) * int(mix["seq_len"])
    return tokens * z["top_k"] * z["held"] / z["experts"]


def gmm_roofline_seconds(config, mix, device_kind, held_pairs=None):
    z = weights_mellum2.sizes(config)
    if held_pairs is None:
        held_pairs = [expected_held_pairs(config, mix)] * z["layers"]
    return flops.roofline_seconds(
        gmm_flops(held_pairs, z), gmm_bytes(held_pairs, z),
        flops.peaks(device_kind))


def matrix_params(config):
    """Parameters every token multiplies: all of them but the held
    experts' stacks (counted by the pair) and the embedding table (a
    lookup)."""
    z = weights_mellum2.sizes(config)
    routed = z["layers"] * 3 * z["held"] * z["d"] * z["d_expert"]
    return weights_mellum2.n_params(config) - routed - z["vocab"] * z["d"]


def train_flops_per_step(config, mix):
    """Model FLOPs of one step of the cell, nothing recomputed, the held
    experts at their expected load."""
    z = weights_mellum2.sizes(config)
    B, S = int(mix["global_batch"]), int(mix["seq_len"])
    return (6.0 * matrix_params(config) * B * S
            + gmm_flops([expected_held_pairs(config, mix)] * z["layers"], z)
            + sum(flash_flops(B, S, z, kind) for kind in KINDS))
