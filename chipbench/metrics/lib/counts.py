"""Operations and bytes a piece of work needs, from its shapes alone.

These are the yardstick's counts: they say what the work requires, not
what a given implementation does, so the same count holds whichever
kernel or program computes it. A multiply-add is two operations.
"""
from __future__ import annotations

import math


# ---------------------------------------------------------------------------
# decoder-only LM (StarCoder2-style: GQA attention, GELU MLP, tied head)
# ---------------------------------------------------------------------------

def lm_matmul_flops_per_token(c: dict) -> float:
    """Weight matmuls of one layer for one token: q, k, v, o and the
    two MLP projections."""
    d, f = c["hidden_size"], c["intermediate_size"]
    nq, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // nq
    return 2.0 * (d * (nq + 2 * nkv) * hd + nq * hd * d + 2 * d * f)


def lm_attention_flops(c: dict, n_keys: int) -> float:
    """Scores and the weighted sum of one layer for one query token that
    attends to ``n_keys`` positions."""
    d, nq = c["hidden_size"], c["num_attention_heads"]
    hd = d // nq
    return 4.0 * nq * hd * n_keys


def lm_head_flops(c: dict) -> float:
    return 2.0 * c["hidden_size"] * c["vocab_size"]


def lm_prefill_flops(c: dict, n: int) -> float:
    """A causal prefill of ``n`` tokens, logits for the last one only."""
    L = c["num_hidden_layers"]
    attn = sum(lm_attention_flops(c, i + 1) for i in range(n)) if n else 0
    return L * (n * lm_matmul_flops_per_token(c) + attn) + (
        lm_head_flops(c) if n else 0.0)


def lm_decode_flops(c: dict, pos: int) -> float:
    """One token at position ``pos`` (attends to ``pos + 1`` keys), with
    its logits."""
    L = c["num_hidden_layers"]
    return L * (lm_matmul_flops_per_token(c)
                + lm_attention_flops(c, pos + 1)) + lm_head_flops(c)


def lm_request_flops(c: dict, prefill: int, decode_from: int,
                     decode_to: int) -> float:
    """A served request: a prefill of ``prefill`` tokens, then one token
    at every position in ``[decode_from, decode_to)``."""
    return lm_prefill_flops(c, prefill) + sum(
        lm_decode_flops(c, p) for p in range(decode_from, decode_to))


# ---------------------------------------------------------------------------
# Zebra kernels: the producer (mask_pack) and the consumer (spmm_cs)
# ---------------------------------------------------------------------------

def _bitmap_bytes(n_blocks: int) -> float:
    return math.ceil(n_blocks / 8)


def mask_pack_cost(M: int, K: int, n_live: float, bs: int, bc: int,
                   item: int) -> tuple[float, float]:
    """(ops, bytes) of turning an (M, K) map into its (payload, bitmap)
    stream: read the map once, write the live blocks and one bit per
    block. One comparison per element."""
    nb = (M // bs) * (K // bc)
    ops = float(M) * K
    byt = float(M) * K * item + n_live * bs * bc * item + _bitmap_bytes(nb)
    return ops, byt


def live_columns(K: int, bc: int, nm: int, zero_frac: float) -> float:
    """Expected block-columns (of ``K // bc``) that hold a live block,
    for ``nm`` block-rows at zero-block fraction ``zero_frac``, blocks
    independent: only those columns' weight rows are needed."""
    return (K // bc) * (1.0 - zero_frac ** nm)


def spmm_cs_cost(M: int, K: int, N: int, n_live: float, bs: int, bc: int,
                 item: int, out_item: int = 4) -> tuple[float, float]:
    """(ops, bytes) of ``mask(x) @ w`` from the stream: a multiply-add per
    live element per output column; read the live payload, the bitmap and
    the weight rows of live block-columns, write the (M, N) output."""
    nm, nk = M // bs, K // bc
    nb = nm * nk
    zf = 1.0 - n_live / nb if nb else 0.0
    ops = 2.0 * n_live * bs * bc * N
    byt = (n_live * bs * bc * item + _bitmap_bytes(nb)
           + live_columns(K, bc, nm, zf) * bc * N * item
           + float(M) * N * out_item)
    return ops, byt


def roofline_seconds(ops: float, byt: float, peak: dict) -> tuple[float, str]:
    """Least time on the chip, and which bound sets it."""
    t_ops = ops / peak["flops_per_s"]
    t_mem = byt / peak["bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


# ---------------------------------------------------------------------------
# CNN (ResNet, NCHW)
# ---------------------------------------------------------------------------

def conv_flops(c_in: int, c_out: int, k: int, h_out: int, w_out: int) -> float:
    return 2.0 * c_in * k * k * c_out * h_out * w_out


def resnet_flops_per_image(c: dict) -> float:
    """Convolutions and the classifier of one forward pass (batch norm,
    ReLU and the Zebra gate are elementwise and not counted)."""
    hw = c["image_hw"]
    chans = c["stage_channels"]
    tot = conv_flops(c["in_channels"], chans[0], c["stem_kernel"], hw, hw)
    c_in = chans[0]
    for si, (n, ch) in enumerate(zip(c["stage_blocks"], chans)):
        for bi in range(n):
            stride = 2 if (si > 0 and bi == 0) else 1
            hw //= stride
            tot += conv_flops(c_in, ch, 3, hw, hw) + conv_flops(ch, ch, 3, hw, hw)
            if stride != 1 or c_in != ch:
                tot += conv_flops(c_in, ch, 1, hw, hw)
            c_in = ch
    return tot + 2.0 * c_in * c["num_classes"]
