"""Operations and bytes of the train step, from shapes alone.

Model FLOPs count what the forward and backward passes require (backward =
2 x forward); work the program recomputes is not counted.  The grouped-GEMM
work is the same whichever backend implements it.
"""

from __future__ import annotations

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


def avg_keys(seq_len: int, window: int) -> float:
    """Mean number of keys a causal query attends to (sliding window
    ``window``, 0 = none)."""
    w = window or seq_len
    return sum(min(i + 1, w) for i in range(seq_len)) / seq_len


def forward_flops_per_token(m: dict, seq_len: int) -> dict:
    """Forward FLOPs per token by part, for a stack of attention + MoE
    blocks.  ``m`` is the configuration's ``model`` section."""
    d, V = m["d_model"], m["vocab_size"]
    H, Hkv = m["num_heads"], m["num_kv_heads"]
    dh = m.get("head_dim") or d // H
    E, k, h = m["num_experts"], m["top_k"], m["moe_d_ff"]
    n_mats = 3 if m.get("ffn_act", "swiglu") == "swiglu" else 2
    L = m["num_layers"]
    local = [("local" in kind) for kind in m["block_pattern"]]
    n_local = L * sum(local) // len(local)
    window = m.get("sliding_window", 0)
    keys = ((L - n_local) * avg_keys(seq_len, 0)
            + n_local * avg_keys(seq_len, window if window else 0))
    return {
        "router": L * 2 * d * E,
        "attn_proj": L * (2 * d * (H + 2 * Hkv) * dh + 2 * H * dh * d),
        "attn_core": 4 * H * dh * keys,   # QK^T and PV over causal keys
        "experts": L * k * n_mats * 2 * d * h,
        "unembed": 2 * d * V,
    }


def train_flops_per_token(m: dict, seq_len: int) -> float:
    return 3.0 * sum(forward_flops_per_token(m, seq_len).values())


def gmm_work(m: dict, tokens: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one train step's grouped GEMMs, forward and
    backward: each of the ``n_mats`` expert matrices is one forward GEMM
    and two backward GEMMs (dX, dW) over the ``tokens * top_k`` routed rows.
    Each GEMM reads or writes two row operands and one expert-bank operand
    once, at the compute dtype's width."""
    d, E, k, h = m["d_model"], m["num_experts"], m["top_k"], m["moe_d_ff"]
    n_mats = 3 if m.get("ffn_act", "swiglu") == "swiglu" else 2
    rows = tokens * k
    b = _DTYPE_BYTES[m["dtype"]]
    n_gemm = 3 * n_mats * m["num_layers"]
    flops = n_gemm * 2.0 * rows * d * h
    nbytes = n_gemm * float(rows * (d + h) + E * d * h) * b
    return flops, nbytes
