"""Fused logarithmic-posit MAC matmul — the EULER-ADAS datapath as one kernel.

One ``pl.pallas_call`` realizes the paper's six-stage pipeline per VMEM tile:

  Stage 1  bounded-posit decode           (unrolled fixed-depth regime scan —
                                           the TPU analogue of the paper's
                                           bit-width-invariant decoder)
  Stage 2  stage-adaptive ILM w/ trunc    (two-plane identity: val/rem)
  Stage 3  exponent & regime scaling      (power-of-2 unit factors built by
                                           exponent-field bit construction)
  Stage 4  quire accumulation             (f32 VMEM accumulator tile,
                                           revisited across the K grid dim)
  Stage 5/6 rounding & result encoding    (separate codec kernel; the matmul
                                           emits the f32 quire value)

Inputs are posit *patterns* at any unsigned width that holds the format
(uint8/uint16 words, or uint32), read at that width and widened inside the
kernel, so HBM traffic is the posit word width — the memory-footprint
advantage the paper argues for.

Hardware notes:
  * no ``clz``: leading-one detection uses the f32-exponent trick with a
    one-step correction, safe for mantissas up to 2^30;
  * MXU does the two dots per tile; VPU does decode — they overlap;
  * grid = (M/bm, N/bn, K/bk), K innermost ("arbitrary"), accumulating into
    the output block which is revisited for all k; ``logmac_experts`` runs
    the same body over a stack of experts with a leading expert grid axis;
  * a grid step walks its (bk, bn) weight block in 128x128 sub-tiles with
    an in-kernel loop and decodes each 128-deep slice of the activation
    block once for all the block's columns: the decode, not the MXU,
    bounds the kernel, and a large block pays the activation decode and
    the per-step overhead once per many weight tiles.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.engine import EulerConfig

# the kernels' op names in compiled programs and traces
NAME = "logmac"
EXPERTS_NAME = "logmac_experts"
# the sub-tile a grid step walks its blocks in: one MXU-wide tile
SUB = 128


def _u(x):
    return jnp.asarray(x, jnp.uint32)


def _mask(n: int):
    return jnp.uint32((1 << n) - 1) if n < 32 else jnp.uint32(0xFFFFFFFF)


def _exp2i(e):
    """Exact 2^e for int32 e in [-126, 127], built from f32 exponent bits."""
    bits = (jnp.clip(e, -126, 127) + 127).astype(jnp.uint32) << 23
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _pow2(e):
    """Exact 2^e for |e| up to ~250 via two balanced factors."""
    h1 = e // 2
    h2 = e - h1
    return _exp2i(h1) * _exp2i(h2)


def _u2f(x):
    """uint32 -> f32 for values below 2^31 (every mantissa here).  Mosaic
    has no uint32 -> f32 cast; the int32 route is exact in that range."""
    return x.astype(jnp.int32).astype(jnp.float32)


def _leading_one_pos(x):
    """Floor(log2(x)) for uint32 1 <= x < 2^31 (f32-exponent trick +
    correction)."""
    xf = _u2f(x)
    bits = jax.lax.bitcast_convert_type(xf, jnp.uint32)
    pos = ((bits >> 23) & jnp.uint32(0xFF)).astype(jnp.int32) - 127
    # conversion may round up to the next power of two; correct one step
    over = ((x >> pos.clip(0, 31).astype(jnp.uint32)) & 1) == 0
    return jnp.where(over, pos - 1, pos)


def _clear_top_bits(x, k: int):
    """Clear the top k set bits of uint32 x (unrolled, clz-free)."""
    for _ in range(k):
        nz = x > 0
        pos = _leading_one_pos(jnp.where(nz, x, jnp.uint32(1)))
        x = jnp.where(nz, x & ~(jnp.uint32(1) << pos.astype(jnp.uint32)), x)
    return x


def decode_planes_raw(pat, pc, stages: int, trunc: int | None,
                      sublane: int | None):
    """Posit patterns -> (val, rem) f32 ILM planes.  Pure jnp; runs inside the
    kernel body and is also unit-tested directly against ref.ref_planes."""
    N, es, W = pc.n_bits, pc.es, pc.frac_window
    rcap = pc.rcap
    p = _u(pat) & _mask(N)
    sign = (p >> (N - 1)) & jnp.uint32(1)
    body = jnp.where(sign == 1, (jnp.uint32(0) - p) & _mask(N - 1), p & _mask(N - 1))
    is_special = (p & _mask(N)) == 0
    is_special |= p == jnp.uint32(1 << (N - 1))

    r0 = (body >> (N - 2)) & jnp.uint32(1)
    # fixed-depth regime scan: rcap iterations (R for bounded — the paper's
    # constant-depth decoder; N-1 for standard posit)
    run = jnp.zeros(p.shape, jnp.int32)
    cont = jnp.ones(p.shape, bool)
    for j in range(rcap):
        bit = (body >> jnp.uint32(N - 2 - j)) & jnp.uint32(1)
        cont = cont & (bit == r0)
        run = run + cont.astype(jnp.int32)
    sat = run >= rcap
    rw = jnp.where(sat, rcap, run + 1)
    k = jnp.where(r0 == 1, run - 1, -run)

    rem_bits = (body << rw.astype(jnp.uint32)) & _mask(N - 1)
    if es > 0:
        e = (rem_bits >> (N - 1 - es)).astype(jnp.int32)
        frac = rem_bits & _mask(N - 1 - es)
    else:
        e = jnp.zeros_like(k)
        frac = rem_bits
    scale = k * (1 << es) + e

    # operand truncation (m bits after the leading one; SIMD sub-lane cap)
    m = trunc
    if sublane is not None:
        m = min(m, sublane - 1) if m is not None else sublane - 1
    if m is not None and m < W:
        drop = W - m
        frac = (frac >> drop) << drop

    mant = (jnp.uint32(1) << W) | frac
    rem_mant = _clear_top_bits(mant, stages)

    sgn = jnp.where(sign == 1, -1.0, 1.0)
    unit = sgn * _pow2(scale - W)
    val = unit * _u2f(mant)
    rem = unit * _u2f(rem_mant)
    val = jnp.where(is_special, 0.0, val)
    rem = jnp.where(is_special, 0.0, rem)
    return val.astype(jnp.float32), rem.astype(jnp.float32)


def decode_planes(pat, ecfg: EulerConfig):
    return decode_planes_raw(pat, ecfg.posit, ecfg.stages, ecfg.trunc,
                             ecfg.sublane)


def _logmac_kernel(a_ref, b_ref, o_ref, *, ecfg: EulerConfig, k_axis: int,
                   sub_k: int, sub_n: int):
    """One grid step: the (bm, bk) activation block against the (bk, bn)
    weight block, walked in (sub_k, sub_n) sub-tiles.  Each activation
    sub-tile is decoded once and serves every column sub-tile of the
    block; each output sub-tile adds its K sub-tiles in increasing order,
    as a grid of (bm, sub_n, sub_k) tiles would, so the sums are the
    same."""
    @pl.when(pl.program_id(k_axis) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # HIGHEST: the planes carry more mantissa bits than one bf16 MXU pass
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)
    two_planes = ecfg.stages > 0 and ecfg.mode == "euler"

    def k_step(kk, carry):
        ks = pl.ds(pl.multiple_of(kk * sub_k, sub_k), sub_k)
        va, ra = decode_planes(a_ref[:, ks], ecfg)

        def n_step(j, carry):
            ns = pl.ds(pl.multiple_of(j * sub_n, sub_n), sub_n)
            vb, rb = decode_planes(b_ref[ks, ns], ecfg)
            acc = dot(va, vb)
            if two_planes:
                acc = acc - dot(ra, rb)
            o_ref[:, ns] += acc
            return carry

        return jax.lax.fori_loop(0, b_ref.shape[-1] // sub_n, n_step, carry)

    jax.lax.fori_loop(0, a_ref.shape[-1] // sub_k, k_step, 0)


@functools.partial(jax.jit, static_argnames=("ecfg", "bm", "bn", "bk", "interpret"))
def logmac(a_pat, b_pat, ecfg: EulerConfig, bm: int = 128, bn: int = 128,
           bk: int = 128, interpret: bool = True):
    """Fused EULER-ADAS matmul on posit patterns.

    a_pat: (M, K) posit patterns, b_pat: (K, N); each unsigned, at least
    as wide as the format, and read at its own width (the kernel widens).
    Returns (M, N) f32 — the quire (f32-accumulated) ILM product.
    ``bm``/``bn``/``bk`` are a grid step's block extents; the step walks
    its block in ``sub_tile(bk)`` x ``sub_tile(bn)`` sub-tiles, and gives
    the same f32 words as a grid of one sub-tile per step.
    """
    return _call(a_pat, b_pat, ecfg, bm, bn, bk, interpret, NAME)


@functools.partial(jax.jit, static_argnames=("ecfg", "bm", "bn", "bk", "interpret"))
def logmac_experts(a_pat, b_pat, ecfg: EulerConfig, bm: int = 128,
                   bn: int = 128, bk: int = 128, interpret: bool = True):
    """``logmac`` for each of E experts at once: a_pat (E, M, K), b_pat
    (E, K, N) -> (E, M, N) f32.  One kernel, the same body, with a leading
    grid axis over the experts: grid (E, M/bm, N/bn, K/bk), so the grid
    follows the operands' shapes alone."""
    assert a_pat.shape[0] == b_pat.shape[0], (a_pat.shape, b_pat.shape)
    return _call(a_pat, b_pat, ecfg, bm, bn, bk, interpret, EXPERTS_NAME)


def sub_tile(block: int) -> int:
    """The kernel's sub-tile extent within a block of ``block``: one MXU
    tile of 128, or the whole block where it is smaller."""
    sub = min(SUB, block)
    assert block % sub == 0, f"block {block} is not a multiple of {SUB}"
    return sub


def grid_counts(lead: tuple, M: int, K: int, N: int, bm: int, bn: int,
                bk: int) -> tuple[int, int]:
    """(grid steps, sub-tile products) of one call over ``lead`` experts:
    a sub-tile product is one (bm, sub_k) x (sub_k, sub_n) pair of dots."""
    steps = math.prod(lead) * -(-M // bm) * -(-N // bn) * -(-K // bk)
    return steps, steps * (bk // sub_tile(bk)) * (bn // sub_tile(bn))


def vmem_bytes(bm: int, bn: int, bk: int, a_bytes: int, b_bytes: int) -> int:
    """VMEM one grid step holds: the double-buffered activation, weight and
    f32 output blocks, and one sub-tile's decoded f32 planes of each
    operand."""
    sk, sn = sub_tile(bk), sub_tile(bn)
    blocks = bm * bk * a_bytes + bk * bn * b_bytes + bm * bn * 4
    planes = 2 * 4 * (bm * sk + sk * sn)
    return 2 * blocks + planes


def _call(a_pat, b_pat, ecfg, bm, bn, bk, interpret, name):
    """The kernel over a_pat (..., M, K) and b_pat (..., K, N), with at
    most one leading (expert) axis, which becomes the grid's first."""
    lead = a_pat.shape[:-2]
    M, K = a_pat.shape[-2:]
    K2, N = b_pat.shape[-2:]
    assert K == K2, (a_pat.shape, b_pat.shape)
    # pad to block multiples with the zero pattern (posit zero ⇒ contributes 0)
    Mp, Np, Kp = (-M % bm), (-N % bn), (-K % bk)
    no_pad = ((0, 0),) * len(lead)
    if Mp or Kp:
        a_pat = jnp.pad(a_pat, no_pad + ((0, Mp), (0, Kp)))
    if Kp or Np:
        b_pat = jnp.pad(b_pat, no_pad + ((0, Kp), (0, Np)))
    Mt, Nt, Kt = (M + Mp) // bm, (N + Np) // bn, (K + Kp) // bk
    if lead:
        grid = (lead[0], Mt, Nt, Kt)
        in_specs = [pl.BlockSpec((None, bm, bk), lambda e, i, j, k: (e, i, k)),
                    pl.BlockSpec((None, bk, bn), lambda e, i, j, k: (e, k, j))]
        out_spec = pl.BlockSpec((None, bm, bn), lambda e, i, j, k: (e, i, j))
    else:
        grid = (Mt, Nt, Kt)
        in_specs = [pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
                    pl.BlockSpec((bk, bn), lambda i, j, k: (k, j))]
        out_spec = pl.BlockSpec((bm, bn), lambda i, j, k: (i, j))

    out = pl.pallas_call(
        functools.partial(_logmac_kernel, ecfg=ecfg, k_axis=len(grid) - 1,
                          sub_k=sub_tile(bk), sub_n=sub_tile(bn)),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(lead + (M + Mp, N + Np), jnp.float32),
        interpret=interpret,
        name=name,
    )(a_pat, b_pat)
    return out[..., :M, :N]
