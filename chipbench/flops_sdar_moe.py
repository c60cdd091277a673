"""Operations and bytes an ``sdar_moe`` block-diffusion training step
needs, from shapes alone and, for the held experts, from how many (row,
choice) pairs were routed to them (the yardstick's own arithmetic, beside
``flops.py``, whose peaks table and roofline rule it uses, as
``flops_mellum2.py`` is for its family; the held experts' and the flash
kernels' byte counts are ``flops_qwen3next.py``'s, the same forms).
Nothing here imports the program.

A step runs ``2 L`` rows a document (the clean copy and the noised one)
through the layers and ``L`` through the head.  Model FLOPs: 6 a
parameter a row for every parameter of a MATRIX the rows pass (the
projections and the routers over ``2 L`` rows, the head over ``L``; the
embedding table is a lookup), 6 a parameter a PAIR routed to a held
expert, and attention by the (query, key) pairs the MASK lets attend — ``L
(L + B) / 2`` clean-clean, ``L (L - B) / 2`` noisy-clean and ``L B``
noisy-noisy: ``L (L + B)`` a head row, not the tiles a kernel visits and
not the causal triangle of ``2 L`` rows, which is twice as many — 4 a
pair a head dimension forward, twice that backward.  Nothing recomputed.
"""

from chipbench import flops, weights_sdar_moe
from chipbench.flops_qwen3next import flash_bytes, gmm_bytes, gmm_flops


def attended_pairs(doc_len, block):
    """(query, key) pairs a head row of one document's ``2 L`` rows
    attends under the block-diffusion mask."""
    return doc_len * (doc_len + int(block))


def rows(mix):
    """Rows a step runs through the layers: both copies."""
    return 2 * int(mix["global_batch"]) * int(mix["seq_len"])


def flash_flops(mix, z):
    """Needed FLOPs of attention forward + backward for one step: 12 a
    pair a head dimension."""
    return (12.0 * attended_pairs(int(mix["seq_len"]), z["block"])
            * int(mix["global_batch"]) * z["heads"] * z["d_head"]
            * z["layers"])


def flash_roofline_seconds(config, mix, device_kind):
    """The least time of the three flash kernels of every layer: the
    mask's pairs' FLOPs and ``flops_qwen3next.flash_bytes``' twelve
    passes over the ``2 L`` rows with K and V at their own heads (the
    mask moves no fewer bytes: every query, key and value is read)."""
    z = weights_sdar_moe.sizes(config)
    return flops.roofline_seconds(
        flash_flops(mix, z),
        flash_bytes(int(mix["global_batch"]), 2 * int(mix["seq_len"]), z,
                    z["layers"]), flops.peaks(device_kind))


def expected_held_pairs(config, mix):
    """Pairs a step a layer routes to the held experts when every expert
    is as likely as another."""
    z = weights_sdar_moe.sizes(config)
    return rows(mix) * z["top_k"] * z["held"] / z["experts"]


def gmm_roofline_seconds(config, mix, device_kind, held_pairs=None):
    z = weights_sdar_moe.sizes(config)
    if held_pairs is None:
        held_pairs = [expected_held_pairs(config, mix)] * z["layers"]
    return flops.roofline_seconds(
        gmm_flops(held_pairs, z), gmm_bytes(held_pairs, z),
        flops.peaks(device_kind))


def matrix_params(config):
    """``(layers', head's)`` parameters every row multiplies: all of them
    but the held experts' stacks (counted by the pair) and the embedding
    table (a lookup); the head apart, since only the noisy rows reach
    it."""
    z = weights_sdar_moe.sizes(config)
    routed = z["layers"] * 3 * z["held"] * z["d"] * z["d_expert"]
    head = z["vocab"] * z["d"]
    return weights_sdar_moe.n_params(config) - routed - 2 * head, head


def train_flops_per_step(config, mix):
    """Model FLOPs of one step of the cell, nothing recomputed, the held
    experts at their expected load."""
    z = weights_sdar_moe.sizes(config)
    through, head = matrix_params(config)
    return (6.0 * through * rows(mix) + 6.0 * head * rows(mix) / 2
            + gmm_flops([expected_held_pairs(config, mix)] * z["layers"], z)
            + flash_flops(mix, z))
