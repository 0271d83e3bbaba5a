"""Operations and bytes that the algorithm needs, from shapes alone.

Whatever implements a step, these count the work the mathematics asks
for: causal attention is counted over the lower triangle, recomputation
is never counted, and a weight is read once. A share of a peak built on
them cannot pass 100 % unless the time leaves out part of the work.

``cfg`` uses GPT-2's published key names (``n_vocab, n_ctx, n_embd,
n_head, n_layer``) and ``n_inner``.
"""

from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matrix multiplication for every
    token: the blocks' projections and MLP, and the vocabulary head."""
    d, f = cfg["n_embd"], cfg["n_inner"]
    return cfg["n_layer"] * (4 * d * d + 2 * d * f) + d * cfg["n_vocab"]


def param_count(cfg: dict) -> int:
    """Every parameter of the repo's GPT-2 block (untied, biased head)."""
    d, f, v = cfg["n_embd"], cfg["n_inner"], cfg["n_vocab"]
    per_layer = 4 * d * d + 4 * d + 2 * d * f + f + d + 4 * d
    return (v * d + cfg["n_ctx"] * d + cfg["n_layer"] * per_layer
            + 2 * d + d * v + v)


def attention_flops(cfg: dict, seq: int, *, causal: bool = True) -> float:
    """Forward FLOPs of one layer's QK^T and PV for ONE sequence."""
    pairs = seq * (seq + 1) / 2 if causal else seq * seq
    return 2 * 2 * pairs * cfg["n_embd"]


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward FLOPs per token of a full causal sequence of ``seq``."""
    attn = cfg["n_layer"] * attention_flops(cfg, seq) / seq
    return 2 * matmul_params(cfg) + attn


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward plus backward (twice the forward); nothing recomputed."""
    return 3 * forward_flops_per_token(cfg, seq)


def flash_fwd_work(cfg: dict, batch: int, seq: int, itemsize: int = 2):
    """(FLOPs, bytes) of one layer's causal attention forward over
    ``batch`` sequences: reads q, k, v, writes o."""
    flops = batch * attention_flops(cfg, seq)
    bytes_ = 4 * batch * seq * cfg["n_embd"] * itemsize
    return flops, bytes_


def flash_bwd_work(cfg: dict, batch: int, seq: int, itemsize: int = 2):
    """(FLOPs, bytes) of one layer's attention backward. It needs four
    products (dV, dP, dQ, dK), twice the forward's two; forming QK^T
    again is recomputation and is not counted. Reads q, k, v, o, do;
    writes dq, dk, dv."""
    flops = 2 * batch * attention_flops(cfg, seq)
    bytes_ = 8 * batch * seq * cfg["n_embd"] * itemsize
    return flops, bytes_


def decode_step_flops(cfg: dict, contexts) -> float:
    """FLOPs one decode step needs for the slots active in it;
    ``contexts`` holds each active slot's context length (tokens its new
    query attends over)."""
    n = len(contexts)
    attn = 2 * 2 * cfg["n_embd"] * cfg["n_layer"] * float(sum(contexts))
    return n * 2 * matmul_params(cfg) + attn


def kv_bytes_per_token(cfg: dict, kv_dtype: str) -> int:
    """Pool bytes one cached position pins over all layers, K and V; an
    int8 position also carries one float32 scale per head, K and V."""
    per = 2 * cfg["n_layer"] * cfg["n_embd"]
    if kv_dtype == "int8":
        return per + 2 * cfg["n_layer"] * cfg["n_head"] * 4
    return per * {"bf16": 2, "fp32": 4}[kv_dtype]


def decode_step_bytes(cfg: dict, live_tokens: int, kv_dtype: str,
                      weight_itemsize: int = 2) -> float:
    """Bytes one decode step has to read: the multiplied weights once in
    the compute dtype, and the live K/V positions at the pool's dtype."""
    return (matmul_params(cfg) * weight_itemsize
            + live_tokens * kv_bytes_per_token(cfg, kv_dtype))


def roofline_seconds(flops: float, bytes_: float, peaks: dict):
    """(least seconds the chip could take, which bound sets it)."""
    t_c = flops / peaks["bf16_flops"]
    t_m = bytes_ / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
