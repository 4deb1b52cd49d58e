"""Paged flash-decode: page-table-gathered posit KV attention in one pass.

The serving tier stores KV state as posit *words* in a shared page pool
(``repro.serving.kvcache``): one ``[num_pages, page_size, KV, hd]`` buffer
per layer, with per-slot page tables mapping logical cache positions to
physical pages.  This module provides decode attention over that layout:

* :func:`paged_attention_reference` — gather-then-attend in plain jnp,
  numerically IDENTICAL to the dense decode path in ``models/layers.py``
  (same dot dimension-numbers, same mask/softmax, injected ``dot_fn`` so
  the caller's backend/policy — including ``faulty:``/``guarded:``
  composition — resolves qk/pv exactly as the dense path would).  This is
  what the ``exact``/``lax_ref`` backends run and what the parity tests
  pin.

* :func:`paged_flash_decode` — the fused Pallas kernel: per page block it
  does posit decode (Stage 1) -> stage-adaptive ILM planes (Stage 2,
  reusing :func:`logmac.decode_planes_raw`) -> log-domain QK -> online
  softmax -> posit re-encode of the probabilities -> ILM PV, gathering
  pages through the page table with scalar-prefetch index maps so refill
  never copies cache contents.  HBM traffic for the cache is the posit
  word width; only f32 running (m, l, acc) tiles live in VMEM.

Page-table conventions (shared with ``serving/kvcache.py``):

* page ``NULL_PAGE`` (0) is reserved and never written: unallocated table
  entries point at it, so gathers of not-yet-grown logical pages yield
  exact zeros — the same bytes a dense cache holds in untouched slots.
  This is what makes paged decode BIT-identical to dense, not just close:
  per-tensor ``pre_scale`` and softmax see the same values either way.
* page ``TRASH_PAGE`` (1) is reserved as a write sink: masked decode rows
  (retired/inactive slots) redirect their cache write there instead of
  predicating the store.  It never appears in any slot's table.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import posit as _P
from repro.core.engine import EulerConfig
from .logmac import decode_planes_raw
from .posit_codec import encode_body

NULL_PAGE = 0   # read-only all-zeros page; target of unallocated table slots
TRASH_PAGE = 1  # write-only sink page for masked rows; never in a table
RESERVED_PAGES = 2
NAME = "paged_flash_decode"  # the kernel's op name in programs and traces


def gather_pages(pages, table):
    """Gather a ``[B, nlp*page_size, ...]`` logical cache view.

    pages: ``[P, page_size, ...]`` pool; table: ``[B, nlp]`` int32 physical
    page ids (``NULL_PAGE`` where unallocated).  Pure gather — no copy of
    the pool itself survives the fusion when this feeds an attention dot.
    """
    B, nlp = table.shape
    ps = pages.shape[1]
    g = jnp.take(pages, table, axis=0)          # [B, nlp, ps, ...]
    return g.reshape((B, nlp * ps) + pages.shape[2:])


def decode_words(x, pc, out_dtype=jnp.float32):
    """Posit storage words -> float (identity for float caches)."""
    if pc is not None and jnp.issubdtype(x.dtype, jnp.integer):
        return _P.decode_to_float(_P.from_storage(x, pc), pc, out_dtype)
    return x.astype(out_dtype)


def _default_dot(a, b, dn, op):
    return jax.lax.dot_general(a, b, dn, preferred_element_type=jnp.float32)


def paged_attention_reference(q, k_pages, v_pages, page_table, pos, *,
                              pc=None, softcap=None, window=None,
                              dot_fn=None):
    """Gather-then-attend decode over paged posit KV state.

    Mirrors the dense decode branch of ``models/layers.py`` operation for
    operation (dimension numbers, scale, softcap, mask value, softmax,
    probs dtype) so tokens are bit-identical to a dense cache holding the
    same words: unallocated positions gather ``NULL_PAGE`` zeros, exactly
    the bytes dense holds past the write frontier.

    q: ``[B, 1, H, hd]``; k_pages/v_pages: ``[P, ps, KV, hd]`` posit words
    (or float); page_table: ``[B, nlp]`` int32; pos: ``[B]`` int32 current
    decode positions.  ``dot_fn(a, b, dn, op)`` routes the qk/pv
    contractions (defaults to exact f32).
    """
    dot_fn = dot_fn or _default_dot
    B, T, H, hd = q.shape
    KV = k_pages.shape[2]
    group = H // KV
    kd = decode_words(gather_pages(k_pages, page_table), pc, q.dtype)
    vd = decode_words(gather_pages(v_pages, page_table), pc, q.dtype)
    S = kd.shape[1]

    qg = q.reshape(B, T, KV, group, hd)
    dn_qk = (((4,), (3,)), ((0, 2), (0, 2)))     # contract hd; batch B, KV
    s = dot_fn(qg, kd, dn_qk, "qk")              # [B, KV, T, group, S]
    s = s * (hd ** -0.5)
    s = s.astype(jnp.float32)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)

    pos_b = jnp.asarray(pos, jnp.int32)
    s_pos = jnp.arange(S)
    valid = s_pos[None, :] <= pos_b[:, None]     # [B, S]
    if window is not None:
        w = jnp.asarray(window, jnp.int32)
        valid &= (w < 0) | (s_pos[None, :] > pos_b[:, None] - w)
    s = jnp.where(valid[:, None, None, None, :], s, -1e30)
    probs = jax.nn.softmax(s, axis=-1).astype(vd.dtype)
    dn_pv = (((4,), (1,)), ((0, 1), (0, 2)))
    o = dot_fn(probs, vd, dn_pv, "pv")           # [B, KV, T, group, hd]
    return jnp.moveaxis(o, 1, 2).reshape(B, T, KV * group * hd)


# --------------------------------------------------------------------------
# Fused kernel
# --------------------------------------------------------------------------

def _paged_decode_kernel(pt_ref, pos_ref, win_ref, scl_ref, q_ref, k_ref,
                         v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                         pc_cache: _P.PositConfig, cfg_qk: EulerConfig,
                         cfg_pv: EulerConfig, softcap, page_size: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Stage 1+2: posit decode -> ILM planes.  q was pre-encoded with the
    # qk policy format (per-tensor pow2 scale folded into scl); k/v are the
    # cache's storage words decoded with the qk/pv stage-adaptive settings.
    # Every KV head rides in one [ps, KV*hd] page block; q is block-diagonal
    # (row h holds head h in its KV head's lanes, posit zero elsewhere), so
    # one dot per plane scores every head against its own KV head only.
    qv, qr = decode_planes_raw(q_ref[0], cfg_qk.posit, cfg_qk.stages,
                               cfg_qk.trunc, cfg_qk.sublane)   # [H, KV*hd]
    kw = k_ref[0].astype(jnp.uint32)                           # [ps, KV*hd]
    kv_, kr = decode_planes_raw(kw, pc_cache, cfg_qk.stages,
                                cfg_qk.trunc, cfg_qk.sublane)

    # log-domain QK via the two-plane ILM identity
    hi = jax.lax.Precision.HIGHEST
    dot = lambda x, y: jax.lax.dot_general(
        x, y, (((1,), (1,)), ((), ())), precision=hi,
        preferred_element_type=jnp.float32)
    s = dot(qv, kv_)                                           # [H, ps]
    if cfg_qk.stages > 0 and cfg_qk.mode == "euler":
        s = s - dot(qr, kr)
    s = s * scl_ref[0]
    if softcap:
        s = softcap * jnp.tanh(s / softcap)

    spos = (jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
            + j * page_size)
    pos = pos_ref[b]
    w = win_ref[0]
    ok = (spos <= pos) & ((w < 0) | (spos > pos - w))
    s = jnp.where(ok, s, -1e30)

    # online softmax (flash-decode running max / sum)
    m_prev = m_ref[...]                                        # [H, 1]
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    pexp = jnp.exp(s - m_new)                                  # [H, ps]
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * alpha + pexp.sum(-1, keepdims=True)

    # Stage 5/6 for the probabilities: posit re-encode with the pv format,
    # then the pv ILM planes against the decoded V words.
    pv_cfg_pc = cfg_pv.posit
    ppat = encode_body(pexp, pv_cfg_pc)
    pv_, pr = decode_planes_raw(ppat, pv_cfg_pc, cfg_pv.stages,
                                cfg_pv.trunc, cfg_pv.sublane)  # [H, ps]
    vw = v_ref[0].astype(jnp.uint32)                           # [ps, KV*hd]
    vv, vr = decode_planes_raw(vw, pc_cache, cfg_pv.stages,
                               cfg_pv.trunc, cfg_pv.sublane)
    dotv = lambda x, y: jax.lax.dot_general(
        x, y, (((1,), (0,)), ((), ())), precision=hi,
        preferred_element_type=jnp.float32)
    o = dotv(pv_, vv)                                          # [H, KV*hd]
    if cfg_pv.stages > 0 and cfg_pv.mode == "euler":
        o = o - dotv(pr, vr)
    acc_ref[...] = acc_ref[...] * alpha + o

    # last page wins: normalized output written every step (no epilogue grid)
    o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


@functools.partial(jax.jit, static_argnames=(
    "pc", "cfg_qk", "cfg_pv", "softcap", "interpret"))
def paged_flash_decode(q, k_pages, v_pages, page_table, pos, window=None, *,
                       pc: _P.PositConfig, cfg_qk: EulerConfig,
                       cfg_pv: EulerConfig, softcap=None,
                       interpret: bool = True):
    """Fused paged decode attention over posit-word pages.

    q ``[B, 1, H, hd]`` float; k_pages/v_pages ``[P, ps, KV, hd]`` integer
    posit storage words in format ``pc``; page_table ``[B, nlp]`` int32;
    pos ``[B]`` int32; window: None / int / traced int32 (<0 = global).
    Returns ``[B, 1, H*hd]`` f32.  Grid is (B, pages) with the page index
    innermost.  Each step DMAs one physical page with all KV heads as a
    ``[ps, KV*hd]`` block (full trailing dims: legal for 8-, 16- and 32-bit
    words alike); the page table, positions, window and scale ride as
    scalar-prefetch operands in SMEM.
    """
    B, T, H, hd = q.shape
    assert T == 1, "flash-decode is single-token"
    P_, ps, KV, _ = k_pages.shape
    group = H // KV
    nlp = page_table.shape[1]

    # pre-encode q once with the qk operand format (per-tensor pow2 scale,
    # as engine.operand_planes does): planes scale linearly, so the scale
    # and the 1/sqrt(hd) factor fold into one post-dot scalar.
    qf = q[:, 0].astype(jnp.float32)                           # [B, H, hd]
    if cfg_qk.pre_scale:
        from repro.core.engine import _pow2_scale
        sq = _pow2_scale(qf)
    else:
        sq = jnp.float32(1.0)
    qpat = encode_body(qf / sq, cfg_qk.posit)
    own = (jnp.arange(H) // group)[:, None] == jnp.arange(KV)[None, :]
    qpat = jnp.where(own[None, :, :, None], qpat[:, :, None, :],
                     jnp.uint32(0)).reshape(B, H, KV * hd)
    scl = (sq * (hd ** -0.5)).reshape(1).astype(jnp.float32)
    win = jnp.full((1,), -1 if window is None else window, jnp.int32)

    kernel = functools.partial(
        _paged_decode_kernel, pc_cache=pc, cfg_qk=cfg_qk, cfg_pv=cfg_pv,
        softcap=softcap, page_size=ps)
    row = pl.BlockSpec((1, H, KV * hd), lambda b, j, *_: (b, 0, 0))
    page = pl.BlockSpec((1, ps, KV * hd),
                        lambda b, j, pt, *_: (pt[b, j], 0, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, nlp),
            in_specs=[row, page, page],
            out_specs=row,
            scratch_shapes=[
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, KV * hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, KV * hd), jnp.float32),
        interpret=interpret,
        name=NAME,
    )(jnp.asarray(page_table, jnp.int32), jnp.asarray(pos, jnp.int32), win,
      scl, qpat, k_pages.reshape(P_, ps, KV * hd),
      v_pages.reshape(P_, ps, KV * hd))
    # row h attended in every KV head's lanes; keep its own head's block
    out = out.reshape(B, KV, group, KV, hd)
    kv = jnp.arange(KV)
    out = jnp.moveaxis(out[:, kv, :, kv, :], 0, 1)             # [B,KV,g,hd]
    return out.reshape(B, 1, H * hd)
