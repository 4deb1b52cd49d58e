"""Pluggable numerics backends behind a string registry.

A backend executes the op set (``dot_general``, ``matmul``, ``qk``, ``pv``,
``elementwise``) under a given :class:`~repro.core.engine.EulerConfig`.  All
backends share one call signature, so models/serving/benchmarks pick their
execution engine by name:

  "exact"    FP32 ``lax.dot_general`` — ignores the config's approximation
             knobs entirely (golden reference).
  "lax_ref"  the pure-lax reference engine (``repro.core.engine``): posit
             quantization + two-plane ILM as composable jnp ops.  Fully
             differentiable (STE) — the training path.
  "pallas"   the fused Pallas kernels (``repro.kernels.ops``): posit codec +
             logmac matmul in two kernel launches (``logmac_experts`` for a
             held stack of expert matrices), and the fused paged
             flash-decode kernel for posit-word KV pages (interpret mode on
             the CPU).  Forward/inference path; ops the kernels do not cover
             (other batched dot_generals, non-"euler" modes, elementwise,
             float KV pages) fall back to the reference engine so any model
             runs end-to-end.

``register_backend`` adds new engines (e.g. a future TPU-native or GPU
backend) without touching any call site.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import posit as _P
from repro.core import engine as _E
from repro.core.engine import EulerConfig

from . import stored as _S


class Backend:
    """Op-set protocol.  Subclasses must implement ``dot_general`` and
    ``elementwise``; the named ops default to dot_general with the canonical
    dimension numbers and may be overridden for fused implementations."""

    name = "base"
    # whether dot_general takes a held weight (``stored.PositWeight``) as
    # is; every other backend is handed its float operand
    reads_words = False

    # -- required ---------------------------------------------------------

    def dot_general(self, a, b, dimension_numbers, cfg: EulerConfig):
        raise NotImplementedError

    def elementwise(self, a, b, cfg: EulerConfig):
        raise NotImplementedError

    # -- derived ----------------------------------------------------------

    def matmul(self, a, b, cfg: EulerConfig):
        """a @ b: contract a's last dim with b's first."""
        dn = (((a.ndim - 1,), (0,)), ((), ()))
        return self.dot_general(a, b, dn, cfg)

    def qk(self, q, k, cfg: EulerConfig):
        """Attention scores over the last dim: [..., T, D] x [..., S, D]."""
        nd = q.ndim
        batch = tuple(range(nd - 2))
        dn = (((nd - 1,), (nd - 1,)), (batch, batch))
        return self.dot_general(q, k, dn, cfg)

    def pv(self, p, v, cfg: EulerConfig):
        """Attention values: [..., T, S] x [..., S, D]."""
        nd = p.ndim
        batch = tuple(range(nd - 2))
        dn = (((nd - 1,), (nd - 2,)), (batch, batch))
        return self.dot_general(p, v, dn, cfg)

    def decode_attention(self, q, k_pages, v_pages, page_table, pos,
                         nctx, path, *, pc=None, softcap=None, window=None):
        """Paged decode attention: gather-then-attend reference.

        Unlike the rest of the op set this receives the full (nctx, path)
        pair: the inner qk/pv contractions re-dispatch through the op
        layer, so policy resolution and wrapper composition
        (``faulty:``/``guarded:``) behave exactly as the dense decode
        path's ``N.dot_general`` calls would — which is what keeps paged
        decode bit-identical to dense under every backend stack.
        """
        from repro.kernels import paged_decode as _PD
        from . import api as _api

        def dot_fn(a, b, dn, op):
            return _api.dot_general(a, b, dn, nctx, op=op, path=path)

        return _PD.paged_attention_reference(
            q, k_pages, v_pages, page_table, pos, pc=pc, softcap=softcap,
            window=window, dot_fn=dot_fn)


class ExactBackend(Backend):
    """FP32 reference: every op runs exact regardless of the config."""

    name = "exact"

    def dot_general(self, a, b, dimension_numbers, cfg: EulerConfig):
        return _E.euler_dot_general(a, b, dimension_numbers,
                                    cfg.replace(mode="exact"))

    def elementwise(self, a, b, cfg: EulerConfig):
        return a * b


class LaxRefBackend(Backend):
    """The composable-jnp reference engine (differentiable, STE grads)."""

    name = "lax_ref"

    def dot_general(self, a, b, dimension_numbers, cfg: EulerConfig):
        return _E.euler_dot_general(a, b, dimension_numbers, cfg)

    def elementwise(self, a, b, cfg: EulerConfig):
        return _E.ilm_elementwise(a, b, cfg)


def _single_contraction(a, b, dimension_numbers):
    """((perm'd a, perm'd b) | None: operands reordered so the one
    contracting dim is a's last / b's first — the fused kernel's layout."""
    (lc, rc), (lb, rb) = dimension_numbers
    if lb or rb or len(lc) != 1 or len(rc) != 1:
        return None
    la, ra = lc[0], rc[0]
    perm_a = tuple(d for d in range(a.ndim) if d != la) + (la,)
    perm_b = (ra,) + tuple(d for d in range(b.ndim) if d != ra)
    return jnp.transpose(a, perm_a), jnp.transpose(b, perm_b)


def _tile(extent: int, cap: int = 128) -> int:
    """Kernel tile: hardware-aligned 128 cap, shrunk (8-multiple) for small
    extents so interpret mode does not pad tiny ops to full MXU tiles."""
    return min(cap, max(8, -(-extent // 8) * 8))


# VMEM a ``logmac`` grid step may hold (``logmac.vmem_bytes``): three
# quarters of the v5e's 16 MiB default scoped limit, the rest left to the
# decode's temporaries
LOGMAC_VMEM = 12 << 20


def _block_choices(extent: int) -> list[int]:
    """``logmac`` block extents for ``extent``, largest first: the
    multiples of 128 that divide its 128-padded extent, so a block pads an
    operand no further than a 128 tile does (a held weight whose extent is
    a multiple of 128 not at all); one small tile below 128."""
    if extent < 128:
        return [_tile(extent)]
    n = -(-extent // 128)
    return [128 * d for d in range(n, 0, -1) if n % d == 0]


def _blocks(M: int, K: int, N: int, a_bytes: int, b_bytes: int,
            bm=None, bn=None, bk=None) -> tuple[int, int, int]:
    """``logmac``'s (bm, bn, bk) for an (M, K) x (K, N) call whose words
    take ``a_bytes`` and ``b_bytes``: the widest weight block, then the
    deepest, whose grid step fits ``LOGMAC_VMEM``.  The block sets how
    often the activation is decoded (once per ``bn`` columns) and how many
    grid steps pay the per-step cost; rows stay one tile.  Given extents
    are kept."""
    from repro.kernels.logmac import vmem_bytes
    bm = bm or _tile(M)
    bns = [bn] if bn else _block_choices(N)
    bks = [bk] if bk else _block_choices(K)
    for n in bns:
        for k in bks:
            if vmem_bytes(bm, n, k, a_bytes, b_bytes) <= LOGMAC_VMEM:
                return bm, n, k
    return bm, bns[-1], bks[-1]


class PallasBackend(LaxRefBackend):
    """Fused posit-codec + logmac kernel path (forward/inference).

    Covers single-contraction, batch-free dot_generals in ``mode="euler"``
    (the paper's engine mode); everything else falls back to the reference
    engine.  ``pre_scale``/``out_quant`` are applied around the kernel with
    the exact same math as the reference path, so both backends agree within
    kernel tolerance.

    A held weight (``stored.PositWeight``) whose words are at the resolved
    config's format goes to ``logmac`` as its stored words and scale: only
    the activation is scaled and encoded per call.  A held stack of
    expert matrices contracted batch-by-expert (``[E, M, K] x [E, K, N]``,
    the MoE block's) goes to ``logmac_experts`` the same way, each
    expert's activation rows with a scale of their own.  Any other config
    (a ladder level at another width, a non-euler rule) contracts its
    float operand per call.
    """

    name = "pallas"
    reads_words = True

    def __init__(self, interpret: bool | None = None,
                 bm: int | None = None, bn: int | None = None,
                 bk: int | None = None):
        self.interpret = interpret
        self.bm, self.bn, self.bk = bm, bn, bk

    def dot_general(self, a, b, dimension_numbers, cfg: EulerConfig):
        if isinstance(b, _S.PositWeight):
            if jnp.size(a) and self._reads(b, jnp.ndim(a),
                                           dimension_numbers, cfg):
                _S.count(_S.STORED)
                return self._stored_dot(a, b, dimension_numbers, cfg)
            _S.count(_S.PER_CALL)
            b = b.operand()
        if cfg.mode != "euler":
            return super().dot_general(a, b, dimension_numbers, cfg)
        pair = _single_contraction(a, b, dimension_numbers)
        if pair is None:
            return super().dot_general(a, b, dimension_numbers, cfg)
        a2, b2 = pair
        K = a2.shape[-1]
        if K != b2.shape[0] or a2.size == 0 or b2.size == 0:
            return super().dot_general(a, b, dimension_numbers, cfg)
        rhs_free = b2.shape[1:]
        N = int(np.prod(rhs_free)) if rhs_free else 1
        bf = b2.reshape(K, N).astype(jnp.float32)
        sb = None
        if cfg.pre_scale:  # same per-tensor power-of-2 centering as the engine
            sb = _E._pow2_scale(bf)
            bf = bf / sb
        b_pat = self._encode(bf, cfg)
        return self._logmac(a2, b_pat, sb, rhs_free, cfg)

    @staticmethod
    def _reads(w, a_ndim: int, dimension_numbers, cfg: EulerConfig) -> bool:
        """Whether held weight ``w``'s words serve this contraction: the
        config is at their format, and ``w`` is one matrix contracted over
        the axis its words were laid out for, or a stack of them (axis 0
        of both operands a batch axis) contracted over that axis and the
        last of a 3-D activation."""
        (lc, rc), (lb, rb) = dimension_numbers
        if not (cfg.mode == "euler" and cfg.pre_scale and cfg.posit == w.pc
                and len(lc) == 1):
            return False
        if not lb and not rb:
            return rc == (w.axis,) and jnp.ndim(w.words) == 2
        return (lb == rb == (0,) and jnp.ndim(w.words) == 3
                and a_ndim == 3 and lc == (2,) and rc == (w.axis + 1,))

    def _stored_dot(self, a, w, dimension_numbers, cfg: EulerConfig):
        """Contract ``a`` with held weight ``w``'s words: only the
        activation is scaled and encoded here."""
        (lc, _), (lb, _) = dimension_numbers
        la = lc[0]
        if lb:
            return self._logmac_experts(a, w, cfg)
        a2 = jnp.transpose(a, tuple(d for d in range(a.ndim) if d != la)
                           + (la,))
        free = tuple(d for i, d in enumerate(jnp.shape(w.w)) if i != w.axis)
        return self._logmac(a2, w.words, w.scale, free, cfg)

    def _logmac_experts(self, a, w, cfg: EulerConfig):
        """``a`` [E, M, K] against held stack ``w`` (words [E, K, N], one
        scale per expert) through ``logmac_experts``: each expert's rows
        are scaled by a power of 2 of their own and encoded, as one
        matrix's activation is in ``_logmac``."""
        from repro.kernels import ops as _K
        E, M, K = a.shape
        af = a.astype(jnp.float32)
        sa = jax.vmap(_E._pow2_scale)(af)
        pat = self._encode((af / sa[:, None, None]).reshape(E * M, K), cfg)
        pat = pat.reshape(E, M, K)
        out = _K.logmac_experts_matmul(pat, w.words, cfg,
                                       interpret=self.interpret,
                                       **self._tiles(pat, w.words))
        out = out * (sa * w.scale)[:, None, None]
        if cfg.out_quant:
            out = _P.quantize(out, cfg.posit)
        return out.astype(cfg.dtype)

    def _tiles(self, a_pat, b_pat) -> dict:
        """``logmac``'s blocks for activation words ``a_pat`` ``[..., M,
        K]`` against weight words ``b_pat`` ``[..., K, N]`` (the
        constructor's ``bm``/``bn``/``bk`` kept where given), counted for
        ``stored.tally``: the call's grid steps and sub-tile products."""
        from repro.kernels import logmac as _LM
        M, K = a_pat.shape[-2:]
        N = b_pat.shape[-1]
        bm, bn, bk = _blocks(M, K, N, a_pat.dtype.itemsize,
                             b_pat.dtype.itemsize, self.bm, self.bn, self.bk)
        steps, tiles = _LM.grid_counts(a_pat.shape[:-2], M, K, N, bm, bn, bk)
        _S.count(_S.LOGMAC_STEPS, steps)
        _S.count(_S.LOGMAC_TILES, tiles)
        return dict(bm=bm, bn=bn, bk=bk)

    def _encode(self, x, cfg: EulerConfig):
        from repro.kernels import ops as _K  # deferred: keeps core import-light
        return _K.encode(x, cfg.posit, interpret=self.interpret)

    def _logmac(self, a2, b_pat, sb, rhs_free, cfg: EulerConfig):
        """``a2`` (contracted dim last) through the codec and ``logmac``
        against weight words ``b_pat`` ``[K, N]`` of scale ``sb`` (None:
        unscaled); the result takes ``a2``'s free dims + ``rhs_free``."""
        from repro.kernels import ops as _K
        K = b_pat.shape[0]
        lhs_free = a2.shape[:-1]
        M = int(np.prod(lhs_free)) if lhs_free else 1
        af = a2.reshape(M, K).astype(jnp.float32)
        if sb is not None:
            sa = _E._pow2_scale(af)
            af = af / sa
        a_pat = self._encode(af, cfg)
        out = _K.logmac_matmul(a_pat, b_pat, cfg, interpret=self.interpret,
                               **self._tiles(a_pat, b_pat))
        if sb is not None:
            out = out * (sa * sb)
        if cfg.out_quant:
            out = _P.quantize(out, cfg.posit)
        return out.reshape(lhs_free + tuple(rhs_free)).astype(cfg.dtype)

    def decode_attention(self, q, k_pages, v_pages, page_table, pos,
                         nctx, path, *, pc=None, softcap=None, window=None):
        cfg_qk = nctx.cfg_for(path, "qk")
        cfg_pv = nctx.cfg_for(path, "pv")
        if (pc is None or cfg_qk.mode != "euler" or cfg_pv.mode != "euler"
                or not jnp.issubdtype(jnp.dtype(k_pages.dtype), jnp.integer)):
            # the fused kernel reads posit-word pages in euler mode only;
            # float pages (and other modes) take the gather reference
            return super().decode_attention(
                q, k_pages, v_pages, page_table, pos, nctx, path,
                pc=pc, softcap=softcap, window=window)
        from repro.kernels import ops as _K
        from repro.kernels import paged_decode as _PD
        interp = (self.interpret if self.interpret is not None
                  else _K._default_interpret())
        return _PD.paged_flash_decode(
            q, k_pages, v_pages, page_table, pos, window, pc=pc,
            cfg_qk=cfg_qk, cfg_pv=cfg_pv, softcap=softcap, interpret=interp)


class FaultyBackend(Backend):
    """Fault-injection wrapper: corrupt posit words, then run the base op.

    When a :class:`repro.reliability.faults.FaultPlan` is active (trace-time
    ``faults.inject(plan, key, step)`` — the serving engine threads key/step
    through its decode scan) and matches the dispatched (layer path, op
    kind), the selected operand is encoded to posit words with the
    bit-accurate codec, seeded single-bit flips of the plan's bit role are
    applied, and the corrupted values are handed to the wrapped backend — so
    the flip lands on exactly the word the lax_ref or pallas engine would
    have consumed.  Exact-mode ops (no posit words in the datapath) are
    immune by construction.
    """

    def __init__(self, base: "str | Backend"):
        self.base = get_backend(base)
        self.name = f"faulty:{self.base.name}"

    def _corrupt(self, a, b, cfg: EulerConfig):
        from repro.reliability import faults as _F
        from . import api as _api
        ctx = _F.current()
        if ctx is None or cfg.mode not in ("euler", "posit", "quant_only"):
            return a, b
        plan, key, step = ctx
        op, path = _api.last_dispatch()
        if not plan.matches(path, op):
            return a, b
        if plan.operand in ("a", "both"):
            a = _F.corrupt(a, cfg, plan, key, step,
                           salt=_F.call_salt(path, op, "a"))
        if plan.operand in ("b", "both"):
            b = _F.corrupt(b, cfg, plan, key, step,
                           salt=_F.call_salt(path, op, "b"))
        return a, b

    def dot_general(self, a, b, dimension_numbers, cfg: EulerConfig):
        a, b = self._corrupt(a, b, cfg)
        return self.base.dot_general(a, b, dimension_numbers, cfg)

    def matmul(self, a, b, cfg: EulerConfig):
        a, b = self._corrupt(a, b, cfg)
        return self.base.matmul(a, b, cfg)

    def qk(self, q, k, cfg: EulerConfig):
        q, k = self._corrupt(q, k, cfg)
        return self.base.qk(q, k, cfg)

    def pv(self, p, v, cfg: EulerConfig):
        p, v = self._corrupt(p, v, cfg)
        return self.base.pv(p, v, cfg)

    def elementwise(self, a, b, cfg: EulerConfig):
        a, b = self._corrupt(a, b, cfg)
        return self.base.elementwise(a, b, cfg)


def faulty(base: "str | Backend") -> FaultyBackend:
    """The fault-injection wrapper around ``base``, registered (memoized)
    under ``"faulty:<base>"`` so policies/CLIs can name it like any other
    backend."""
    wrapped = FaultyBackend(base)
    return _BACKENDS.setdefault(wrapped.name, wrapped)


class GuardedBackend(Backend):
    """ABFT guard wrapper: run the base op, verify it, escalate on violation.

    Every contraction-shaped op (``dot_general``/``matmul``/``qk``/``pv``)
    is routed through :func:`repro.reliability.guards.guard_call`: an online
    checksum check against an exact contraction of the posit-quantized
    operands (tolerance calibrated per :class:`EulerConfig`), NaR/regime-
    saturation sentinels on the encoded output, and a ``lax.cond``-gated
    recompute ladder (same precision → wider posit → exact) on violation.
    Per-dispatch counters surface via ``numerics.api.guard_stats()``.

    Composes around any base — ``"guarded:faulty:pallas"`` guards the fused
    kernel path *under* fault injection, the campaign's recovery arm (the
    guard's same-precision retry redraws the fault PRNG stream via
    ``faults.retrying``, modelling transient upsets).  ``elementwise`` has no
    checksum identity and passes through unguarded.
    """

    def __init__(self, base: "str | Backend", gcfg=None):
        from repro.reliability import guards as _G
        self.base = get_backend(base)
        self.gcfg = gcfg if gcfg is not None else _G.DEFAULT
        self.name = f"guarded:{self.base.name}"

    def _guarded(self, kind, a, b, dimension_numbers, cfg):
        from repro.reliability import guards as _G
        return _G.guard_call(self.base, kind, a, b, dimension_numbers,
                             cfg, self.gcfg)

    def dot_general(self, a, b, dimension_numbers, cfg: EulerConfig):
        return self._guarded("dot_general", a, b, dimension_numbers, cfg)

    def matmul(self, a, b, cfg: EulerConfig):
        dn = (((a.ndim - 1,), (0,)), ((), ()))
        return self._guarded("matmul", a, b, dn, cfg)

    def qk(self, q, k, cfg: EulerConfig):
        nd = q.ndim
        batch = tuple(range(nd - 2))
        dn = (((nd - 1,), (nd - 1,)), (batch, batch))
        return self._guarded("qk", q, k, dn, cfg)

    def pv(self, p, v, cfg: EulerConfig):
        nd = p.ndim
        batch = tuple(range(nd - 2))
        dn = (((nd - 1,), (nd - 2,)), (batch, batch))
        return self._guarded("pv", p, v, dn, cfg)

    def elementwise(self, a, b, cfg: EulerConfig):
        return self.base.elementwise(a, b, cfg)


def guarded(base: "str | Backend", gcfg=None) -> GuardedBackend:
    """The ABFT guard wrapper around ``base``, registered (memoized) under
    ``"guarded:<base>"``.  A non-default ``gcfg`` replaces the registered
    instance (one guard policy per name)."""
    wrapped = GuardedBackend(base, gcfg)
    if gcfg is not None:
        return register_backend(wrapped.name, wrapped)
    return _BACKENDS.setdefault(wrapped.name, wrapped)


_BACKENDS: dict[str, Backend] = {}


def register_backend(name: str, backend: Backend) -> Backend:
    """Register (or replace) a backend instance under ``name``."""
    _BACKENDS[name] = backend
    return backend


def get_backend(name: str | Backend) -> Backend:
    """Look up a backend by name (instances pass through unchanged).

    ``"faulty:<base>"`` / ``"guarded:<base>"`` names resolve (and
    self-register) on demand to the fault-injection / ABFT-guard wrapper
    around ``<base>`` — prefixes nest left-to-right, so
    ``"guarded:faulty:pallas"`` guards a faulted pallas path."""
    if isinstance(name, Backend):
        return name
    try:
        return _BACKENDS[name]
    except KeyError:
        if name.startswith("faulty:"):
            return faulty(name.split(":", 1)[1])
        if name.startswith("guarded:"):
            return guarded(name.split(":", 1)[1])
        raise KeyError(f"unknown numerics backend {name!r}; "
                       f"available: {sorted(_BACKENDS)}") from None


def available_backends() -> tuple[str, ...]:
    return tuple(sorted(_BACKENDS))


register_backend("exact", ExactBackend())
register_backend("lax_ref", LaxRefBackend())
register_backend("pallas", PallasBackend())
