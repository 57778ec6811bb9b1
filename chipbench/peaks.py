"""Peaks of each chip the benchmark runs on, and the operations and bytes of
the work a cell asks for, computed from shapes.

The work is counted from the traffic, not from the implementation: a token
decoded at context n costs what the model needs at n, whatever the kernels
do to produce it (dead table columns, padding, logits of positions nobody
reads are not work). So a faster implementation is judged against the same
numbers.
"""

from __future__ import annotations

# keyed by jax.Device.device_kind
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' (system "
                  "architecture): per chip 197 TFLOP/s bf16, 393 TOP/s "
                  "int8, 16 GB HBM2 at 819 GB/s",
    },
}

BF16 = 2      # bytes: activations, KV pages
F32 = 4


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a kind not in the table is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}")
    return PEAKS[device_kind]


# --- model operations ---------------------------------------------------------


def matmul_params(m: dict) -> dict:
    """Multiply-accumulate weights per token: ``layer`` for one block and
    ``head`` for the output projection (the embedding lookup is a gather)."""
    D, F, V = m["d_model"], m["d_ff"], m["vocab_size"]
    if m["family"] == "ssm":
        # r, k, v, g, o and the channel-mix receptance (D x D each), the
        # ddlerp LoRA (D x 5R and 5 x R x D), the decay LoRA (D x RD,
        # RD x D), and the channel-mix key and value (D x F, F x D)
        R, RD = 5 * m["ddlerp_lora_rank"], m["decay_lora_rank"]
        layer = 6 * D * D + 2 * D * R + 2 * D * RD + 2 * D * F
    else:
        q = m["num_heads"] * m["head_dim"]
        kv = m["num_kv_heads"] * m["head_dim"]
        layer = D * q + 2 * D * kv + q * D + 3 * D * F
    return {"layer": layer, "head": D * V}


def mixer_flops(m: dict, n: int) -> float:
    """Token-mixing operations of one token in one layer, with ``n`` the
    tokens it sees (itself included): attention's q.k and p.v over the live
    context, or RWKV's state read (r.S) and update (w*S + k^T v)."""
    if m["family"] == "ssm":
        return 4.0 * m["num_heads"] * m["head_dim"] * m["head_dim"]
    return 4.0 * m["num_heads"] * m["head_dim"] * n


def decode_flops(m: dict, n: int) -> float:
    """One generated token whose step sees ``n`` tokens (itself included)."""
    p = matmul_params(m)
    return (2.0 * (m["num_layers"] * p["layer"] + p["head"])
            + m["num_layers"] * mixer_flops(m, n))


def prefill_flops(m: dict, n: int) -> float:
    """A prompt of ``n`` tokens up to its first token: every position
    through every layer, causal attention, and the head at the last
    position only."""
    p = matmul_params(m)
    if m["family"] == "ssm":
        mix = n * mixer_flops(m, 0)
    else:
        mix = 4.0 * m["num_heads"] * m["head_dim"] * n * (n + 1) / 2
    return 2.0 * n * m["num_layers"] * p["layer"] + m["num_layers"] * mix \
        + 2.0 * p["head"]


# --- paged decode attention -----------------------------------------------------


def paged_attn_work(m: dict, n: int) -> tuple[float, float]:
    """(operations, HBM bytes) of one decode token's attention in one layer
    over ``n`` live cache rows: q.k and p.v over every head, and the live
    K and V rows read once in bfloat16 with the query read and the output
    written."""
    h, kv, dh = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    flops = 4.0 * h * dh * n
    nbytes = 2.0 * kv * dh * n * BF16 + 2.0 * h * dh * BF16
    return flops, nbytes


def roofline_s(flops: float, nbytes: float, pk: dict) -> tuple[float, str]:
    """Least time the chip could take, and which peak bounds it."""
    t_c, t_m = flops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "hbm")
