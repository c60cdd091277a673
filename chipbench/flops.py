"""Operations and bytes the algorithm needs, from shapes alone, the table
of peaks, and the way from a configuration to the module that counts for
its family.  Model FLOPs follow the Megatron convention (6 a parameter a
token, no recomputation counted): the yardstick's own arithmetic, and
since PR 42 the repository's only copy of it."""

import importlib
import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))
#: The families whose module is older than the rule its name now follows.
_OLDER = {"granitemoehybrid": "flops_hybrid", "nemotron_h": "flops_nemotron",
          "qwen3_next": "flops_qwen3next", "mellum": "flops_mellum2",
          "bailing_hybrid": "flops_ling3"}


def family(config):
    """The module that counts for a configuration:
    ``chipbench/flops_<model_type>.py`` (``gpt2`` where the file states no
    ``model_type``).  Each has ``train_flops_per_step(config, mix)`` and,
    for the kernels its family runs, ``<kernel>_roofline_seconds(config,
    mix, device_kind, ...)``; a reader shared between cells asks here for
    its cell's count, so a new family brings a file and edits none."""
    model_type = config.get("model_type", "gpt2")
    return importlib.import_module(
        "chipbench." + _OLDER.get(model_type, "flops_" + model_type))


def peaks(device_kind):
    """Published peaks of one chip; an unknown kind is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise RuntimeError(
            f"no peaks recorded for device kind {device_kind!r} in "
            f"chipbench/peaks.json (known: "
            f"{sorted(k for k in table if k != 'source')})")
    return table[device_kind]


def lm_train_flops_per_token(n_params, seq_len, d_model, n_layers):
    """6 n_params (2 forward + 4 backward) plus causal attention
    12 span_avg d per layer (QK^T and AV are 4 span d forward, backward
    twice that), span_avg = (S + 1) / 2 keys a query, self included."""
    span_avg = (seq_len + 1) / 2.0
    return 6.0 * n_params + 12.0 * span_avg * d_model * n_layers


def causal_attention_flops(batch, seq_len, n_heads, d_head, n_layers):
    """Needed FLOPs of causal attention, forward + backward, for one step:
    the same 12 span_avg d a token a layer as above (d = heads x d_head);
    the masked half is not needed and not counted."""
    span_avg = (seq_len + 1) / 2.0
    return (12.0 * span_avg * n_heads * d_head * n_layers
            * batch * seq_len)


def causal_attention_bytes(batch, seq_len, n_heads, d_head, n_layers,
                           itemsize=2):
    """Least HBM traffic of flash attention forward + backward for one
    step: forward reads Q, K, V and writes O; backward reads Q, K, V, O,
    dO and writes dQ, dK, dV — 12 passes over a (B, S, H, D) tensor in the
    compute type (the per-row log-sum-exp is 1/D of one and left out)."""
    return 12.0 * batch * seq_len * n_heads * d_head * itemsize * n_layers


def roofline_seconds(flops, nbytes, peak):
    """The least time the chip could take, and which bound applies."""
    t_flops = flops / peak["bf16_flops"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return max(t_flops, t_bytes), (
        "compute" if t_flops >= t_bytes else "memory")
