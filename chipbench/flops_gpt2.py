"""Operations and bytes a ``gpt2``-style training step's kernels need,
from shapes alone (beside ``flops.py``, whose counts of causal attention,
peaks table and roofline rule it uses, as ``flops_nemotron.py`` is for
its family).  The family's model FLOPs are
``flops.lm_train_flops_per_token``'s, read by ``model.mfu`` from the
host's clock."""

from chipbench import flops, weights


def flash_roofline_seconds(config, mix, device_kind):
    """Needed FLOPs and least bytes of causal attention forward + backward
    over ``mix``'s rows in every layer, over the peaks."""
    z = weights.sizes(config)
    args = (int(mix["global_batch"]), int(mix["seq_len"]), z["heads"],
            z["d_head"], z["layers"])
    return flops.roofline_seconds(
        flops.causal_attention_flops(*args),
        flops.causal_attention_bytes(*args), flops.peaks(device_kind))
