"""Posit encode/decode Pallas kernels (Stages 1 and 6 of the NCE pipeline).

The encode kernel builds the pattern straight from f32 bit fields (no frexp),
performing pattern-domain RNE exactly like the core codec.  Subnormal f32
inputs are flushed to zero (the paper's DAZ/FTZ policy).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import posit as P
from .logmac import decode_planes_raw, _mask, _u

_G = 26  # guard bits (>= 23 keeps f32 inputs exact)
# the kernels' op names in compiled programs and traces
ENCODE_NAME = "posit_encode"
DECODE_NAME = "posit_decode"


def encode_body(x, pc: P.PositConfig):
    """f32 -> posit pattern, pure jnp bit ops (kernel-safe: no frexp)."""
    N, es, G = pc.n_bits, pc.es, _G
    bits = jax.lax.bitcast_convert_type(jnp.asarray(x, jnp.float32), jnp.uint32)
    sign = bits >> 31
    expf = ((bits >> 23) & jnp.uint32(0xFF)).astype(jnp.int32)
    frac23 = bits & _mask(23)
    is_zero = (expf == 0)                      # zero and subnormals (DAZ)
    is_nar = expf == 255                       # Inf/NaN -> NaR
    scale = expf - 127

    over = scale > pc.max_scale
    under = scale < pc.min_scale
    scale_c = jnp.clip(scale, pc.min_scale, pc.max_scale)
    frac_g = jnp.where(over | under, jnp.uint32(0), frac23 << (G - 23))

    k = scale_c >> es
    e = (scale_c - (k << es)).astype(jnp.int32)
    kmax, kmin, rcap = pc.k_max, pc.k_min, pc.rcap
    pos = k >= 0
    at_hi, at_lo = k == kmax, k == kmin
    if pc.bounded:
        w = jnp.where(pos, jnp.where(at_hi, rcap, k + 2),
                      jnp.where(at_lo, rcap, -k + 1))
        rb = jnp.where(pos,
                       jnp.where(at_hi, _u((1 << rcap) - 1),
                                 ((_u(1) << (k.clip(0) + 1).astype(jnp.uint32)) - 1) << 1),
                       jnp.where(at_lo, _u(0), _u(1)))
    else:
        w = jnp.where(pos, jnp.where(at_hi, N - 1, k + 2), -k + 1)
        rb = jnp.where(pos,
                       jnp.where(at_hi, _mask(N - 1),
                                 ((_u(1) << (k.clip(0) + 1).astype(jnp.uint32)) - 1) << 1),
                       _u(1))
    T = (e.astype(jnp.uint32) << G) | frac_g
    t = (N - 1) - w
    sh = es + G - t
    sh_u = jnp.clip(sh, 1, 31).astype(jnp.uint32)
    half = (_u(1) << (sh_u - 1)) - 1
    lsb = (T >> sh_u) & _u(1)
    T_r = jnp.where(sh > 0, (T + half + lsb) >> sh_u,
                    T << jnp.clip(-sh, 0, 31).astype(jnp.uint32))
    body = (rb << t.clip(0).astype(jnp.uint32)) + T_r
    # clamp with selects: Mosaic cannot lower unsigned min/max (maxui)
    body = jnp.where(body < 1, _u(1), body)
    body = jnp.where(body > _mask(N - 1), _mask(N - 1), body)
    body = jnp.where(over, _mask(N - 1), body)
    body = jnp.where(under, _u(1), body)
    pat = jnp.where(sign == 1, (_u(0) - body) & _mask(N), body)
    pat = jnp.where(is_zero, _u(0), pat)
    pat = jnp.where(is_nar, _u(1 << (N - 1)), pat)
    return pat


def _encode_kernel(x_ref, o_ref, *, pc):
    o_ref[...] = encode_body(x_ref[...], pc)


def _decode_kernel(p_ref, o_ref, *, pc):
    val, _ = decode_planes_raw(p_ref[...], pc, 0, None, None)
    o_ref[...] = val


_MAX_BLOCK_ROWS = 256  # 256 x 1024 words: 1 MiB per buffer in VMEM


def _tiled_elementwise(kernel, name, x, out_dtype, pc, block: int,
                       interpret: bool):
    """Run an elementwise kernel over ``x`` as a lane-dense 2-D array, in
    ``(8 * r, 128 * c)`` tiles: the TPU's (8, 128) block rule.

    A tensor whose last dim is a multiple of 128 is tiled in place as
    ``[prod(leading dims), last]``, so a weight matrix is not copied into
    another layout.  Any other shape is flattened, zero-padded and laid out
    as ``[rows, block]`` with the row count padded to a multiple of 8.
    Blocks are at most ``_MAX_BLOCK_ROWS x block``; a ragged last block is
    masked by Pallas (elementwise, so its padding never reaches the output).
    """
    if block % 128:
        raise ValueError(f"codec block={block} must be a multiple of 128 lanes")
    orig_shape = x.shape
    n = None
    if x.ndim >= 2 and x.size and x.shape[-1] % 128 == 0:
        x2 = x.reshape(-1, x.shape[-1])
    else:
        flat = x.reshape(-1)
        n = flat.shape[0]
        rows = -(-max(n, 1) // block)
        rows = -(-rows // 8) * 8
        flat = jnp.pad(flat, (0, rows * block - n))
        x2 = flat.reshape(rows, block)
    R, C = x2.shape
    br, bc = min(R, _MAX_BLOCK_ROWS), min(C, block)
    spec = pl.BlockSpec((br, bc), lambda i, j: (i, j))
    out = pl.pallas_call(
        functools.partial(kernel, pc=pc),
        grid=(pl.cdiv(R, br), pl.cdiv(C, bc)),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((R, C), out_dtype),
        interpret=interpret,
        name=name,
    )(x2)
    if n is not None:
        out = out.reshape(-1)[:n]
    return out.reshape(orig_shape)


@functools.partial(jax.jit, static_argnames=("pc", "block", "interpret"))
def posit_encode(x, pc: P.PositConfig, block: int = 1024, interpret: bool = True):
    """f32 tensor -> posit patterns (uint32) via the encode kernel."""
    return _tiled_elementwise(_encode_kernel, ENCODE_NAME,
                              jnp.asarray(x, jnp.float32), jnp.uint32, pc,
                              block, interpret)


@functools.partial(jax.jit, static_argnames=("pc", "block", "interpret"))
def posit_decode(pat, pc: P.PositConfig, block: int = 1024, interpret: bool = True):
    """posit patterns -> f32 tensor via the decode kernel."""
    return _tiled_elementwise(_decode_kernel, DECODE_NAME,
                              jnp.asarray(pat, jnp.uint32), jnp.float32, pc,
                              block, interpret)
