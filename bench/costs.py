"""Operations and bytes of the sectored attention kernel and of the model.

Counted from shapes, the way the algorithm needs them, not from the
compiler. Widths come from a configuration file's sizes.
"""

from __future__ import annotations

PAGE = 128  # tokens per KV page (sector)
BF16 = 2
F32 = 4


def kernel_call(sizes: dict, batch: int, selected: int
                ) -> tuple[float, float]:
    """(flops, bytes) of one call of ``sectored_attention_paged`` for one
    layer: every slot of the wave (``batch``), every KV head, ``selected``
    pages each. Reads the grouped query (bf16), the K and V rows of each
    selected page of each head; writes the f32 output and the f32 mass of
    each selected page. Operations: the two contractions, q.k and p.v."""
    H, G, hd = sizes["n_heads"], sizes["n_kv_heads"], sizes["head_dim"]
    rows = batch * G * selected * PAGE
    flops = 2 * 2 * batch * H * selected * PAGE * hd
    read = batch * H * hd * BF16 + 2 * rows * hd * BF16
    write = batch * H * hd * F32 + batch * G * selected * F32
    return float(flops), float(read + write)


def roofline_s(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """Least time on the chip, and which bound sets it."""
    t_c = flops / peaks["bf16_flops"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def matmul_params(sizes: dict) -> int:
    """Weights a token multiplies through: every layer's projections and
    MLP, and the head (the embedding is a lookup)."""
    D, H, G, hd = (sizes["d_model"], sizes["n_heads"], sizes["n_kv_heads"],
                   sizes["head_dim"])
    layer = D * hd * (H + 2 * G) + H * hd * D + 3 * D * sizes["d_ff"]
    return sizes["n_layers"] * layer + sizes["vocab"] * D


def token_flops(sizes: dict, attended: int) -> float:
    """Model FLOPs of one token that attends ``attended`` earlier tokens
    (itself included) in every layer."""
    attn = 2 * 2 * sizes["n_heads"] * sizes["head_dim"] * attended
    return float(2 * matmul_params(sizes) + sizes["n_layers"] * attn)


def attended_decode(position: int, selected: int) -> int:
    """Tokens a sectored decode step at ``position`` attends: every valid
    token while the valid pages fit the selection, else the full pages
    selected beside the newest, partial one."""
    valid_pages = position // PAGE + 1
    if valid_pages <= selected:
        return position + 1
    return (selected - 1) * PAGE + position % PAGE + 1
