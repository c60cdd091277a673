"""Operations and bytes a ``granitemoehybrid`` training step needs, from
shapes alone (the yardstick's own arithmetic, beside ``flops.py``, whose
peaks table and roofline rule it uses).

Model FLOPs follow ``flops.lm_train_flops_per_token``'s convention — 6 a
parameter a token (the convolution's taps and the vectors ride along at
well under 0.01%), causal attention in the attention layers only, nothing
recomputed — plus what the state-space **recurrence** needs.  The program
computes the recurrence in its chunked dual form, which spends more
operations than these to reach the matrix unit; they are not counted, so
``step.mfu`` and ``kernel.ssd_roofline`` cannot be raised by a costlier
way of computing the same thing.
"""

from chipbench import flops, weights_hybrid


def ssd_flops(batch, seq_len, z):
    """Needed FLOPs of the recurrence, forward + backward, for one step: a
    token and head, ``H = a H + (dt x) B^T`` is 3 P N (decay, product,
    sum), ``y = H C`` 2 P N; backward twice the forward."""
    per_token_head = 15.0 * z["ssm_d_head"] * z["ssm_state"]
    mamba_layers = sum(1 for k in z["kinds"] if k == "mamba")
    return per_token_head * z["ssm_heads"] * batch * seq_len * mamba_layers


def ssd_bytes(batch, seq_len, z, itemsize=2):
    """Least HBM traffic of the scan, forward + backward, for one step:
    forward reads x, B, C (compute type) and dt (float32) and writes y;
    backward reads those and dy and writes dx, dB, dC and ddt.  The
    states between blocks, which the recurrence itself would not write,
    are left out."""
    x = z["ssm_heads"] * z["ssm_d_head"] * itemsize
    bc = 2 * z["ssm_groups"] * z["ssm_state"] * itemsize
    dt = z["ssm_heads"] * 4
    per_token = (2 * x + bc + dt) + (3 * x + 2 * bc + 2 * dt)
    mamba_layers = sum(1 for k in z["kinds"] if k == "mamba")
    return float(per_token) * batch * seq_len * mamba_layers


def train_flops_per_step(config, mix):
    """Model FLOPs of one step of the cell, nothing recomputed."""
    z = weights_hybrid.sizes(config)
    B, S = int(mix["global_batch"]), int(mix["seq_len"])
    attention_layers = sum(1 for k in z["kinds"] if k == "attention")
    return (6.0 * weights_hybrid.n_params(config) * B * S
            + flops.causal_attention_flops(
                B, S, z["heads"], z["d_head"], attention_layers)
            + ssd_flops(B, S, z))


def ssd_roofline_seconds(config, mix, device_kind):
    z = weights_hybrid.sizes(config)
    B, S = int(mix["global_batch"]), int(mix["seq_len"])
    return flops.roofline_seconds(
        ssd_flops(B, S, z), ssd_bytes(B, S, z), flops.peaks(device_kind))
