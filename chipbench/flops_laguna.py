"""Operations and bytes a ``laguna`` training step needs, from shapes
alone and, for the held experts, from how many (token, choice) pairs were
routed to them (the yardstick's own arithmetic, beside ``flops.py``, whose
peaks table and roofline rule it uses, as ``flops_mellum2.py`` is for its
family; the held experts' counts are ``flops_qwen3next.py``'s, the same
forms).  Nothing here imports the program.

Model FLOPs: 6 a parameter a token for every parameter of a MATRIX all
tokens pass (the projections and gates at each row's own head count, the
dense FFN, the shared experts, the routers, the head; the vectors ride
along; the embedding table is a lookup and is not counted), 6 a parameter
a PAIR routed to a held expert, and attention by the (query, key) pairs a
row ATTENDS AT ITS OWN QUERY HEADS: the band's ``W (W + 1) / 2 + (S - W)
W`` in a ``sliding_attention`` layer, the triangle's ``S (S + 1) / 2`` in
a ``full_attention`` one — 4 a pair a head dimension forward, twice that
backward — whatever tiles a kernel visits to cover them.  Nothing
recomputed.
"""

from chipbench import flops, weights_laguna
from chipbench.flops_mellum2 import attended_pairs
from chipbench.flops_qwen3next import gmm_bytes, gmm_flops

KINDS = ("sliding_attention", "full_attention")


def heads_of(z, kind):
    """The query heads of every kept layer of ``kind``, a layer."""
    return [h for h, k in zip(z["heads"], z["kinds"]) if k == kind]


def reach(z, kind):
    return z["window"] if kind == "sliding_attention" else None


def sparse_layers(z):
    return sum(1 for f in z["ffns"] if f == "sparse")


def flash_flops(batch, seq_len, z, kind):
    """Needed FLOPs of attention forward + backward in the layers of
    ``kind`` for one step: 12 a pair a head dimension a query head."""
    return (12.0 * attended_pairs(seq_len, reach(z, kind)) * batch
            * sum(heads_of(z, kind)) * z["d_head"])


def flash_bytes(batch, seq_len, z, kind, itemsize=2):
    """Least HBM traffic of grouped-query flash attention forward +
    backward in the layers of ``kind``:
    ``flops.causal_attention_bytes``' twelve passes, six of them (K, V
    forward; K, V, dK, dV backward) at the key/value heads' width (a
    window moves no fewer bytes: every query, key and value is read)."""
    return sum(6.0 * (h + z["kv_heads"]) * z["d_head"] * itemsize
               * batch * seq_len for h in heads_of(z, kind))


def flash_roofline_seconds(config, mix, device_kind, kind=None):
    """The least time of the flash kernels of the layers of ``kind``
    (None: of every attention layer, both kinds' needs added up)."""
    z = weights_laguna.sizes(config)
    B, S = int(mix["global_batch"]), int(mix["seq_len"])
    kinds = KINDS if kind is None else (kind,)
    return flops.roofline_seconds(
        sum(flash_flops(B, S, z, k) for k in kinds),
        sum(flash_bytes(B, S, z, k) for k in kinds),
        flops.peaks(device_kind))


def expected_held_pairs(config, mix):
    """Pairs a step a sparse layer routes to the held experts when every
    expert is as likely as another."""
    z = weights_laguna.sizes(config)
    tokens = int(mix["global_batch"]) * int(mix["seq_len"])
    return tokens * z["top_k"] * z["held"] / z["experts"]


def gmm_roofline_seconds(config, mix, device_kind, held_pairs=None):
    z = weights_laguna.sizes(config)
    if held_pairs is None:
        held_pairs = [expected_held_pairs(config, mix)] * sparse_layers(z)
    return flops.roofline_seconds(
        gmm_flops(held_pairs, z), gmm_bytes(held_pairs, z),
        flops.peaks(device_kind))


def matrix_params(config):
    """Parameters every token multiplies: all of them but the held
    experts' stacks (counted by the pair) and the embedding table (a
    lookup)."""
    z = weights_laguna.sizes(config)
    routed = sparse_layers(z) * 3 * z["held"] * z["d"] * z["d_expert"]
    return weights_laguna.n_params(config) - routed - z["vocab"] * z["d"]


def train_flops_per_step(config, mix):
    """Model FLOPs of one step of the cell, nothing recomputed, the held
    experts at their expected load."""
    z = weights_laguna.sizes(config)
    B, S = int(mix["global_batch"]), int(mix["seq_len"])
    return (6.0 * matrix_params(config) * B * S
            + gmm_flops(
                [expected_held_pairs(config, mix)] * sparse_layers(z), z)
            + sum(flash_flops(B, S, z, kind) for kind in KINDS))
