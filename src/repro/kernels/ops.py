"""Public jit'd wrappers for the Pallas kernels.

``euler_matmul_fused(x, w, ecfg)`` is the end-to-end fused path: f32 inputs
are posit-encoded (codec kernel), multiplied through the fused logmac kernel,
and returned as the f32 quire value — the whole EULER-ADAS NCE in two kernel
launches.  ``interpret`` defaults to True on the CPU and False on a TPU;
any other platform is refused rather than silently interpreted.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.engine import EulerConfig
from . import logmac as _logmac
from . import posit_codec as _codec


@functools.cache
def _default_interpret() -> bool:
    """True on the CPU (Pallas interpreter), False on a TPU (Mosaic).

    Cached: jax.default_backend() initializes the platform on first call and
    is not free per kernel launch; the backend is fixed per process."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"the Pallas kernels target the TPU (or the CPU interpreter); "
        f"platform {platform!r} is not supported")


def encode(x, pc, block: int = 1024, interpret: bool | None = None):
    it = _default_interpret() if interpret is None else interpret
    return _codec.posit_encode(x, pc, block=block, interpret=it)


def decode(pat, pc, block: int = 1024, interpret: bool | None = None):
    it = _default_interpret() if interpret is None else interpret
    return _codec.posit_decode(pat, pc, block=block, interpret=it)


def logmac_matmul(a_pat, b_pat, ecfg: EulerConfig, bm: int = 128,
                  bn: int = 128, bk: int = 128, interpret: bool | None = None):
    it = _default_interpret() if interpret is None else interpret
    return _logmac.logmac(a_pat, b_pat, ecfg, bm=bm, bn=bn, bk=bk, interpret=it)


def logmac_experts_matmul(a_pat, b_pat, ecfg: EulerConfig, bm: int = 128,
                          bn: int = 128, bk: int = 128,
                          interpret: bool | None = None):
    it = _default_interpret() if interpret is None else interpret
    return _logmac.logmac_experts(a_pat, b_pat, ecfg, bm=bm, bn=bn, bk=bk,
                                  interpret=it)


def euler_matmul_fused(x, w, ecfg: EulerConfig, interpret: bool | None = None,
                       **tiles):
    """f32 (M,K) @ (K,N) through the full kernelized EULER-ADAS pipeline."""
    pc = ecfg.posit
    a_pat = encode(x, pc, interpret=interpret)
    b_pat = encode(w, pc, interpret=interpret)
    return logmac_matmul(a_pat, b_pat, ecfg, interpret=interpret, **tiles)
