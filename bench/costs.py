"""Operations and bytes of the sectored attention kernel and of the model.

Counted from shapes, the way the algorithm needs them, not from the
compiler. Widths come from a configuration file's sizes; the formulas that
depend on the architecture are its family's (``bench/families/``).
"""

from __future__ import annotations

from bench import families

PAGE = 128  # tokens per KV page (sector)
BF16 = 2
F32 = 4


def _family(sizes: dict):
    # widths written out by hand name no family: they are dense GQA's
    return families.of(sizes if "family" in sizes
                       else dict(sizes, family="gqa_dense"))


def kernel_call(sizes: dict, batch: int, selected: int
                ) -> tuple[float, float]:
    """(flops, bytes) of one call of ``sectored_attention_paged`` for one
    layer, over ``batch`` slots and ``selected`` pages each."""
    return _family(sizes).kernel_call(sizes, batch, selected)


def roofline_s(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """Least time on the chip, and which bound sets it."""
    t_c = flops / peaks["bf16_flops"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def matmul_params(sizes: dict) -> int:
    """Weights a token multiplies through."""
    return _family(sizes).matmul_params(sizes)


def token_flops(sizes: dict, attended: int) -> float:
    """Model FLOPs of one token that attends ``attended`` earlier tokens
    (itself included)."""
    return _family(sizes).token_flops(sizes, attended)


def attended_decode(position: int, selected: int) -> int:
    """Tokens a sectored decode step at ``position`` attends: every valid
    token while the valid pages fit the selection, else the full pages
    selected beside the newest, partial one."""
    valid_pages = position // PAGE + 1
    if valid_pages <= selected:
        return position + 1
    return (selected - 1) * PAGE + position % PAGE + 1
