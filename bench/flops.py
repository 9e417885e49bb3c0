"""Operations and bytes the algorithm needs, computed from the shapes in a
configuration file (``bench/configs/*.json``, ESM-2 key names).

Recomputed work (activation rematerialisation, a backward kernel that
recomputes the forward's scores) is never counted: these are the least
the chip has to do.
"""
from __future__ import annotations

from typing import Dict

BF16 = 2
F32 = 4


def param_count(c: Dict) -> int:
    """Parameters of a bio_bert encoder: per layer the Q/K/V/O projections
    and the GELU FFN with their biases and two layer norms; a final layer
    norm; the token embedding, tied to the output head."""
    d, ff, n = c["hidden_size"], c["intermediate_size"], c["num_hidden_layers"]
    qkv = c["num_attention_heads"] * c["head_dim"]
    attn = 3 * (d * qkv + qkv) + qkv * d + d
    ffn = d * ff + ff + ff * d + d
    return n * (attn + ffn + 4 * d) + 2 * d + c["vocab_size"] * d


def train_flops_per_token(c: Dict, seq_len: int) -> float:
    """Model FLOPs of one training step per token: 6 per parameter
    (forward 2, backward 4) plus attention's scores and weighted sum over
    ``seq_len`` keys, 12 x layers x d_model x seq_len (forward 4, backward 8)."""
    d, n = c["hidden_size"], c["num_hidden_layers"]
    return 6.0 * param_count(c) + 12.0 * n * d * seq_len


def flash_attention_fwd(b: int, h: int, s: int, d: int) -> Dict[str, float]:
    """One forward call over (b, s, h, d) bf16 Q, K, V with every key live:
    QK^T and PV, 2 x 2 x b x h x s^2 x d FLOPs; reads Q, K, V, writes O and
    the fp32 log-sum-exp rows."""
    return {"flops": 4.0 * b * h * s * s * d,
            "bytes": 4.0 * b * s * h * d * BF16 + b * h * s * F32}


def flash_attention_bwd(b: int, h: int, s: int, d: int) -> Dict[str, float]:
    """One backward (dQ and dK/dV kernels together): dP = dO V^T, dV = P^T dO,
    dQ = dS K, dK = dS^T Q, 4 x 2 x b x h x s^2 x d FLOPs; reads Q, K, V, O,
    dO and the log-sum-exp rows once, writes dQ, dK, dV once."""
    return {"flops": 8.0 * b * h * s * s * d,
            "bytes": 8.0 * b * s * h * d * BF16 + b * h * s * F32}


def least_seconds(work: Dict[str, float], peak: Dict[str, float]) -> float:
    """The roofline: the larger of FLOPs over peak FLOP/s and bytes over
    peak bytes/s."""
    return max(work["flops"] / peak["flops"], work["bytes"] / peak["bytes"])
