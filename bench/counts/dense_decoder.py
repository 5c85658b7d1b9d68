"""Model FLOPs and needed bytes of a dense decoder's serving steps.

Counted from the configuration file's sizes and from what each step
computed (the contexts of its decoded tokens and its prefill chunks), not
from the program: model work only, with no padding lanes, empty slots or
replicated copies.  Matmuls count 2 FLOPs per multiply-add; attention
counts Q.K and P.V over each token's context.
"""

from __future__ import annotations


def dims(conf):
    H, Hk = conf["num_attention_heads"], conf["num_key_value_heads"]
    D = conf.get("head_dim") or conf["hidden_size"] // H
    return (conf["hidden_size"], H, Hk, D, conf["intermediate_size"],
            conf["num_hidden_layers"], conf["vocab_size"])


def layer_params(conf) -> int:
    """Multiply-add weights of one block (projections and gated MLP)."""
    d, H, Hk, D, ff, _, _ = dims(conf)
    return d * (H + 2 * Hk) * D + H * D * d + 3 * d * ff


def weight_bytes(conf, itemsize: int = 2) -> int:
    """Weights a decode step reads: every block, the norms and the LM head
    (the input embedding reads only the batch's rows, left out)."""
    d, _, _, _, _, L, V = dims(conf)
    return itemsize * (L * (layer_params(conf) + 2 * d) + d + V * d)


def kv_bytes_per_token(conf, kv_format: str) -> int:
    """Stored K and V of one position over all layers, with int8 scales."""
    _, _, Hk, D, _, L, _ = dims(conf)
    per_head = {"int8": D + 4, "bf16": 2 * D}[kv_format]
    return 2 * L * Hk * per_head


def attend_flops(conf, ctx: int) -> int:
    """Q.K and P.V of one token over ``ctx`` positions, all layers."""
    _, H, _, D, _, L, _ = dims(conf)
    return 4 * H * D * ctx * L


def token_flops(conf, ctx: int, head: bool) -> int:
    d, _, _, _, _, L, V = dims(conf)
    return (2 * L * layer_params(conf) + attend_flops(conf, ctx)
            + (2 * d * V if head else 0))


def step_flops(conf, decode_ctx, prefill) -> int:
    """Model FLOPs of one scheduler step: each decoded token over its
    context with the LM head; each prefill chunk ``(offset, n, done)``
    over its prefix, with the LM head for a prompt's last token only."""
    total = sum(token_flops(conf, c, True) for c in decode_ctx)
    for off, n, done in prefill:
        total += n * token_flops(conf, 0, False)
        total += attend_flops(conf, 1) * (n * off + n * (n + 1) // 2)
        if done:
            d, _, _, _, _, _, V = dims(conf)
            total += 2 * d * V
    return total


def step_bytes(conf, kv_format: str, decode_ctx, prefill) -> int:
    """Bytes one step needs: the weights once, and the valid KV of every
    decoded token's slot and of each prefill chunk's prefix."""
    ctx = sum(decode_ctx) + sum(off + n for off, n, _ in prefill)
    return weight_bytes(conf) + ctx * kv_bytes_per_token(conf, kv_format)


def attend_bytes(conf, kv_format: str, decode_ctx) -> int:
    """Bytes the decode attend kernels need in one step: each live slot's
    query (f32), its valid K/V with scales, and its output (f32)."""
    _, H, _, D, _, L, _ = dims(conf)
    io = 2 * 4 * H * D * L * len(decode_ctx)
    return io + sum(decode_ctx) * kv_bytes_per_token(conf, kv_format)
