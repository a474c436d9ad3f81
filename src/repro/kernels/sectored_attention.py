"""Pallas TPU kernels: sectored decode attention (the paper's SA+VBL on TPU).

Hardware mapping: the Sector Predictor's page indices are
*scalar-prefetched* so they can steer the BlockSpec index_map — the grid
walks (batch, kv-head, selected-sector) and the DMA engine brings one
selected KV page HBM->VMEM per step. Pages that are not selected are
never read from HBM at all: that is Sectored Activation + Variable Burst
Length — the burst (pipeline of page DMAs) has data-dependent length K
instead of the full sequence.

Two entry points share the steering machinery:

* :func:`sectored_attention` — the reference-shaped kernel
  ((B, Hkv, P, page, hd) KV) asserted **bitwise** against
  ``kernels/ref.py:sectored_attention_ref`` in tier-1.
* :func:`sectored_attention_paged` — the serving kernel over the runtime's
  page-major cache view ((B, P, page, Hkv * hd), a reshape of the
  (B, S, Hkv, hd) decode cache that the compiled step gets without a
  copy). Its KV block is one head's rows of one page, (page, hd): lane
  block h of the page, a shape the TPU tiling rule allows, so each grid
  step moves exactly the sector it attends over. Its unquantized
  arithmetic mirrors ``runtime/sectored_decode.py:sectored_attend``
  operand-for-operand (bf16 matmul operands, f32 accumulation, identical
  mask/softmax/mass formulation), so the fused serving path is bit-exact
  with the dispatch path in interpret mode; compiled, the two agree
  within a tolerance. With int8 pages + per-sector scales it dequantizes
  in the f32 accumulate (tolerance-gated, see kernels/quantized_kv.py).

Softmax note: both kernels stream each fetched page's masked scores (and
its V page) into VMEM scratch and run ONE global softmax + contraction at
the final grid step, rather than the online max/rescale recurrence. An
online softmax multiplies the accumulator by ``exp(m_prev - m_new)`` per
page — a different float expression tree from the two-pass softmax of the
dispatch path, so it can never be bitwise against it. The scratch holds
the K pages along one flat axis — (rep, K * page) f32 scores, (K * page,
hd) V — so the softmax reduces one axis and the contraction is a single
matmul, both of which Mosaic lowers; for serving budgets (K ~ P/8 pages
of 128 x 128 bf16) it sits comfortably inside the ~16 MiB VMEM.

Length convention: ``length`` is the **count** of valid tokens — positions
``0 .. length-1`` exist, mask is ``tok_pos < length``. This matches
``attention.decode_attend`` (which masks ``spos <= cache.length`` with the
new token sitting AT ``cache.length``, i.e. ``cache.length + 1`` valid
rows); the pre-fix kernel treated ``length`` as the newest position and
leaked one extra token whenever a caller passed a count. The newest,
partially-filled page is thereby masked at its true fill — the in-kernel
analogue of the paper's shortened VBL burst on the fractional sector.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend

NEG_INF = -1e30


def _check_page_idx(page_idx, hkv: int) -> bool:
    """Validate page_idx's head axis against the cache and return the
    shared-pages flag the index maps must agree with.

    The grid steers head ``0 if shared else program_id(1)`` through the
    scalar-prefetched index table; a silently-wrong flag would make every
    head walk head 0's pages (or read out of bounds), so shape-vs-flag
    agreement is enforced loudly here instead of trusted per call site.
    """
    if page_idx.ndim != 3:
        raise ValueError(
            f"page_idx must be (B, Hkv, K) or (B, 1, K); got shape "
            f"{page_idx.shape}")
    heads = page_idx.shape[1]
    if heads not in (1, hkv):
        raise ValueError(
            f"page_idx head axis must be 1 (shared sector set) or Hkv="
            f"{hkv}; got {heads} — a mismatched head axis would steer "
            f"every head through the wrong page schedule")
    return heads == 1 and hkv > 1


def _scratch_shapes(rep: int, num_selected: int, page: int, hd: int,
                    v_dtype):
    """Per-(b, h) VMEM scratch both kernels stream pages into, laid out
    for the final contraction: masked scores (rep, K * page), their
    validity (1, K * page), and V (K * page, hd). Page ``i`` lands at the
    128-aligned offset ``i * page`` of the flat axis."""
    return [pltpu.VMEM((rep, num_selected * page), jnp.float32),
            pltpu.VMEM((1, num_selected * page), jnp.float32),
            pltpu.VMEM((num_selected * page, hd), v_dtype)]


def _stream_page(i, s, valid, v, s_scr, valid_scr, v_scr, *, page: int):
    """Store fetched page ``i``'s masked scores, validity and V rows."""
    off = pl.multiple_of(i * page, page)
    s_scr[:, pl.ds(off, page)] = jnp.where(valid, s, NEG_INF)
    valid_scr[:, pl.ds(off, page)] = valid[:1].astype(jnp.float32)
    v_scr[pl.ds(off, page), :] = v


def _global_softmax_attend(s_scr, valid_scr, v_scr, *, page: int):
    """The final-step softmax + contraction both kernels share.

    The scores of all K fetched pages form one flat (rep, K * page) row
    per query head: the max, the exp and the denominator run over that
    axis, and the V contraction is a single-axis matmul (rep, K * page)
    @ (K * page, hd) — shapes Mosaic lowers. The dispatch path reduces
    over the same flat axis, so the two agree op for op. ``e`` meets V
    in V's dtype (bf16 on the serving path, like the dispatch path's
    ``e.astype(v.dtype)``; f32 on the reference and quantized paths).

    Returns ``(out (rep, hd) f32, mass (1, K) f32)``: ``mass`` is each
    page's share of the attention mass summed over the q-head group —
    the SHT update's input, same expression as the dispatch path's.
    """
    scores = s_scr[...]
    valid = jnp.broadcast_to(valid_scr[...] != 0.0, scores.shape)
    m = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.where(valid, jnp.exp(scores - m), 0.0)
    v = v_scr[...]
    num = jnp.dot(e.astype(v.dtype), v, preferred_element_type=jnp.float32)
    den = jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    num_selected = scores.shape[-1] // page
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, num_selected), 1)
    mass = jnp.zeros((1, num_selected), jnp.float32)
    for c in range(num_selected):  # static: K pages per step budget
        mass = mass + jnp.where(
            lane == c, jnp.sum(e[:, c * page:(c + 1) * page]), 0.0)
    return num / den, mass / jnp.maximum(jnp.sum(e), 1e-30)


def _ref_kernel(pages_ref, length_ref,  # scalar prefetch
                q_ref, k_ref, v_ref,  # VMEM blocks
                out_ref,  # VMEM output block
                s_scr, valid_scr, v_scr,  # scratch
                *, page_size: int, num_selected: int, shared_pages: bool):
    b = pl.program_id(0)
    h = 0 if shared_pages else pl.program_id(1)
    i = pl.program_id(2)

    q = q_ref[0, 0].astype(jnp.float32)  # (rep, hd)
    k = k_ref[0, 0, 0].astype(jnp.float32)  # (page, hd)
    s = jnp.einsum("rk,pk->rp", q, k) / jnp.sqrt(jnp.float32(q.shape[-1]))
    valid = _vbl_window(pages_ref[b, h, i], length_ref, b, s.shape,
                        page_size=page_size)
    _stream_page(i, s, valid, v_ref[0, 0, 0].astype(jnp.float32),
                 s_scr, valid_scr, v_scr, page=page_size)

    @pl.when(i == num_selected - 1)
    def _finish():
        out, _ = _global_softmax_attend(s_scr, valid_scr, v_scr,
                                        page=page_size)
        out_ref[0, 0] = out


def _vbl_window(page_id, length_ref, b, shape, *, page_size: int):
    """Validity of each token slot in the fetched page: the shortened-burst
    window. ``length`` is a count; the newest page is valid only up to its
    fill (``length % page_size``), the VBL fractional sector."""
    tok_pos = page_id * page_size + jax.lax.broadcasted_iota(
        jnp.int32, shape, 1)
    return tok_pos < length_ref[b]


def _paged_kernel(pages_ref, length_ref, *refs, page_size: int,
                  num_selected: int, shared_pages: bool, quantized: bool):
    if quantized:
        (ks_ref, vs_ref, q_ref, k_ref, v_ref, out_ref, mass_ref,
         s_scr, valid_scr, v_scr) = refs
    else:
        (q_ref, k_ref, v_ref, out_ref, mass_ref,
         s_scr, valid_scr, v_scr) = refs
    b = pl.program_id(0)
    h = pl.program_id(1)
    i = pl.program_id(2)
    page_id = pages_ref[b, 0 if shared_pages else h, i]

    q = q_ref[0, 0]  # (rep, hd) — bf16 operand, like the dispatch path
    k = k_ref[0, 0]  # (page, hd): head h's rows of the selected page
    v = v_ref[0, 0]
    if quantized:
        # dequant in the f32 accumulate: the sector's payload arrived as
        # int8 (half the burst bytes) with its one per-(page, head) scale
        q = q.astype(jnp.float32)
        k = k.astype(jnp.float32) * ks_ref[b, h, i]
        v = v.astype(jnp.float32) * vs_ref[b, h, i]
    s = jnp.einsum("rk,pk->rp", q, k, preferred_element_type=jnp.float32)
    s = s / jnp.sqrt(jnp.float32(q.shape[-1]))
    valid = _vbl_window(page_id, length_ref, b, s.shape, page_size=page_size)
    _stream_page(i, s, valid, v, s_scr, valid_scr, v_scr, page=page_size)

    @pl.when(i == num_selected - 1)
    def _finish():
        out, mass = _global_softmax_attend(s_scr, valid_scr, v_scr,
                                           page=page_size)
        out_ref[0, 0] = out
        mass_ref[0, 0] = mass


@functools.partial(jax.jit, static_argnames=("shared", "interpret"))
def _sectored_attention(q, k_pages, v_pages, page_idx, length, shared: bool,
                        interpret: bool):
    B, Hkv, rep, hd = q.shape
    _, _, P, page, _ = k_pages.shape
    K = page_idx.shape[-1]

    def kv_map(b, h, i, pages, length):
        return (b, h, pages[b, 0 if shared else h, i], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, K),
        in_specs=[
            pl.BlockSpec((1, 1, rep, hd), lambda b, h, i, *_: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, page, hd), kv_map),
            pl.BlockSpec((1, 1, 1, page, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, hd),
                               lambda b, h, i, *_: (b, h, 0, 0)),
        scratch_shapes=_scratch_shapes(rep, K, page, hd, jnp.float32),
    )
    kernel = functools.partial(_ref_kernel, page_size=page,
                               num_selected=K, shared_pages=shared)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rep, hd), jnp.float32),
        interpret=interpret,
        name="sectored_attention",
    )(page_idx, length, q, k_pages, v_pages)


def sectored_attention(q, k_pages, v_pages, page_idx, length,
                       interpret: bool | None = None):
    """q (B,Hkv,rep,hd); k_pages/v_pages (B,Hkv,P,page,hd);
    page_idx (B,Hkv,K) or (B,1,K) int32; length (B,) int32 **count** of
    valid tokens (positions 0..length-1 exist) -> (B,Hkv,rep,hd) f32.

    Bitwise target: ``kernels/ref.py:sectored_attention_ref``.

    A singleton head axis on ``page_idx`` means one **shared sector set per
    sequence** (the serving runtime's ``sector_share_heads`` mode, and the
    layout the shared-prefix demand OR-merge produces): the scalar-prefetched
    index stream is one per sequence and every kv head walks the same page
    schedule. Each head's KV slice is distinct data, so a page DMA per
    (batch, head, step) block still occurs — the win is the Hkv-fold smaller
    index table and a uniform (more prefetch-friendly) page walk, not fewer
    copies. Selected pages arrive in ascending order from
    ``sector_predictor.predict_topk`` (monotone HBM walk).

    ``interpret=None`` auto-detects: compiled on TPU, interpreter elsewhere.
    """
    shared = _check_page_idx(page_idx, q.shape[1])
    return _sectored_attention(q, k_pages, v_pages, page_idx, length,
                               shared=shared,
                               interpret=backend.resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("shared", "interpret"))
def _sectored_attention_paged(q, k_pages, v_pages, page_idx, length,
                              k_scale, v_scale, shared: bool,
                              interpret: bool):
    B, Hkv, rep, hd = q.shape
    _, P, page, _, _ = k_pages.shape
    K = page_idx.shape[-1]
    quantized = k_scale is not None
    # a page's lanes hold (head, dim): head h's rows are lane block h, a
    # (page, hd) block the tiling rule allows, so each step DMAs exactly
    # one head's sector
    k_rows = k_pages.reshape(B, P, page, Hkv * hd)
    v_rows = v_pages.reshape(B, P, page, Hkv * hd)

    def kv_map(b, h, i, pages, *_):
        return (b, pages[b, 0 if shared else h, i], 0, h)

    def head_map(b, h, i, *_):
        return (b, h, 0, 0)

    prefetch = [page_idx, length]
    if quantized:
        # the selected sectors' scales ride in SMEM next to the page
        # table, (B, Hkv, K): one scalar per fetched (page, head), so SMEM
        # grows with the budget K, not with the context
        sel = jnp.broadcast_to(page_idx, (B, Hkv, K))
        prefetch += [jnp.take_along_axis(x.transpose(0, 2, 1), sel, axis=2)
                     for x in (k_scale, v_scale)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B, Hkv, K),
        in_specs=[
            pl.BlockSpec((1, 1, rep, hd), head_map),
            pl.BlockSpec((1, 1, page, hd), kv_map),
            pl.BlockSpec((1, 1, page, hd), kv_map),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, rep, hd), head_map),
            pl.BlockSpec((1, 1, 1, K), head_map),
        ),
        scratch_shapes=_scratch_shapes(
            rep, K, page, hd, jnp.float32 if quantized else v_pages.dtype),
    )
    kernel = functools.partial(_paged_kernel, page_size=page,
                               num_selected=K, shared_pages=shared,
                               quantized=quantized)
    out, mass = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((B, Hkv, rep, hd), jnp.float32),
                   jax.ShapeDtypeStruct((B, Hkv, 1, K), jnp.float32)),
        interpret=interpret,
        name="sectored_attention_paged",
    )(*prefetch, q, k_rows, v_rows)
    return out, mass.reshape(B, Hkv, K)


def sectored_attention_paged(q, k_pages, v_pages, page_idx, length, *,
                             k_scale=None, v_scale=None,
                             interpret: bool | None = None):
    """Serving-path fused kernel over the page-major cache view.

    q (B,Hkv,rep,hd) — the runtime's grouped query (bf16 in serving);
    k_pages/v_pages (B,P,page,Hkv,hd) — ``cache.k.reshape(B, -1, page,
    Hkv, hd)``, a free reshape of the decode cache;
    page_idx (B,Hkv,K) or (B,1,K) int32; length (B,) int32 count of valid
    tokens **including** the token appended this step (the runtime passes
    ``cache.length + 1``).

    Returns ``(out (B,Hkv,rep,hd) f32, mass (B,Hkv,K) f32)`` — ``out``
    before the caller's ``.astype(x.dtype)`` and output projection,
    ``mass`` the per-selected-page attention mass for the SHT update.

    Unquantized (``k_scale is None``): arithmetic mirrors
    ``sectored_attend``'s gather+attend operand-for-operand — bf16 matmul
    operands with f32 accumulation, ``e`` cast to the V dtype before the
    output contraction — so fused and dispatch paths are **bitwise**
    identical (the tier-1 oracle).

    Quantized: ``k_pages``/``v_pages`` are int8 with per-(sequence, page,
    kv-head) scales ``k_scale``/``v_scale`` (B,P,Hkv) f32. The selected
    pages' scales are gathered to (B,Hkv,K) and scalar-prefetched beside
    the page table, and each page is dequantized in the kernel's f32
    accumulate. Tolerance-gated, not bit-gated.
    """
    shared = _check_page_idx(page_idx, k_pages.shape[3])
    if (k_scale is None) != (v_scale is None):
        raise ValueError("pass both k_scale and v_scale, or neither")
    return _sectored_attention_paged(
        q, k_pages, v_pages, page_idx, length, k_scale, v_scale,
        shared=shared, interpret=backend.resolve_interpret(interpret))
