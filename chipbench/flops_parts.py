"""Operations the model's parts need, from the cell's configuration alone
(beside ``flops.py``, whose peaks table it is read against).

The dense FFNs: forward, the gradient of the input and the gradient of
the weights are each 2 x tokens x d x d_ff FLOPs a matrix; two matrices
for ``gelu`` / ``relu2``, three for ``swiglu``; every layer that has one.
Needed work: the forward recomputed under ``remat`` is not counted, so a
share of the peak read against these cannot be raised by computing more.
A chip's own tokens: the global batch over the devices.
"""

from chipbench import weights, weights_hybrid


def ffn_shape(config):
    """``(d, d_ff, matrices, layers)`` of the configuration's dense FFNs,
    ``None`` where it has none this file knows."""
    if "n_inner" in config:                       # the gpt2 family: gelu
        z = weights.sizes(config)
        return z["d"], z["d_ff"], 2, z["layers"]
    if config.get("model_type") == "granitemoehybrid":       # SwiGLU
        z = weights_hybrid.sizes(config)
        return z["d"], z["d_ff"], 3, z["layers"]
    if config.get("model_type") == "bailing_hybrid":  # the leading SwiGLUs
        return (config["hidden_size"], config["intermediate_size"], 3,
                min(config["first_k_dense_replace"], config["n_layer"]))
    return None


def ffn_train_flops(config, mix, n_devices):
    """Needed FLOPs of the dense FFNs for one chip's tokens of one step."""
    shape = ffn_shape(config)
    if shape is None:
        return None
    d, d_ff, matrices, layers = shape
    tokens = int(mix["global_batch"]) * int(mix["seq_len"]) / n_devices
    return 3 * 2.0 * tokens * d * d_ff * matrices * layers
