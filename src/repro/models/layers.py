"""Composable layers.  Every matmul routes through ``repro.numerics``.

Functional style: ``*_init(key, ...) -> params dict`` and
``*_apply(params, x, ctx) -> y``.  ``Ctx`` carries the ``NumericsContext``
(precision policy + backend; a plain ``EulerConfig`` still works and is
promoted to a uniform policy), the mesh (for activation sharding
constraints) and cache state for decoding.

Layer-path scopes for policy matching: attention traces under ``attn``, MLPs
under ``mlp``, MoE under ``moe``, SSM under ``ssm`` (and the LM head under
``head`` — see transformer.py), so a ``PrecisionPolicy`` rule like
``("*attn*", P8)`` hits exactly the attention ops.

Exact-path policy (paper Stage 5: "approximation is confined to mantissa
multiplication; normalization, rounding and exception handling remain
exact"): norms, softmax, RoPE, router logits and elementwise nonlinearities
run in exact f32; all large matmuls run through ``repro.numerics``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as PS

from repro import numerics as N
from repro.core import posit as _P
from repro.core.engine import EulerConfig
from repro.numerics import NumericsContext


def cache_encode(x, cache_dtype, pc=None):
    """Write-side KV-cache codec: integer caches store posit words — the
    paper's posit memory-compression applied to the KV cache.

    The format follows the storage width (uint8 -> Posit-(8,0), uint16 ->
    Posit-(16,1), uint32 -> Posit-(32,2)) unless ``pc`` names the active
    policy's format of the same width (e.g. a bounded-regime B-Posit), in
    which case the policy format is kept end-to-end — Fixed-Posit's
    store-the-words-you-compute-with argument."""
    pc = _P.storage_pc(cache_dtype, pc)
    if pc is not None:
        return _P.to_storage(_P.encode_from_float(x, pc), pc)
    return x.astype(cache_dtype)


def cache_decode(x, out_dtype=jnp.bfloat16, pc=None):
    pc = _P.storage_pc(x.dtype, pc)
    if pc is not None:
        return _P.decode_to_float(_P.from_storage(x, pc), pc, out_dtype)
    return x


def cache_policy_pc(ctx, cache_dtype):
    """The posit format a KV cache of ``cache_dtype`` stores under the
    active policy: the attention qk operand format when its width matches
    the storage width, else the standard posit of that width; ``None`` for
    float caches.  Resolved at trace time under the ``attn`` scope."""
    cfg_qk = N.resolve("qk", ctx=ctx.numerics)
    pref = cfg_qk.posit if cfg_qk.mode != "exact" else None
    return _P.storage_pc(cache_dtype, pref)


@dataclasses.dataclass
class Ctx:
    ecfg: EulerConfig | None = None  # legacy uniform config (still honoured)
    numerics: NumericsContext | None = None  # policy + backend (wins if set)
    mesh: Any = None                 # jax Mesh or None
    data_axes: tuple = ("pod", "data")
    model_axis: str = "model"
    decode_pos: Any = None           # decode position: scalar (lockstep
                                     # batch) or [B] per-slot vector
    page_table: Any = None           # [B, n_logical] int32 physical page ids
                                     # — presence selects paged decode
    decode_write: Any = None         # [B] bool write mask for paged decode
                                     # (False rows write the trash page)
    deterministic: bool = True
    moe_fsdp: bool = False           # expert weights 2D-sharded (model, data)
    attn_head_shard: bool = False    # shard q/k/v heads over model in
                                     # prefill/train (kills the per-layer
                                     # full-T k/v all-gather — §Perf)
    moe_gather_dtype: Any = None     # cast expert weights before the ZeRO-3
                                     # all-gather (bf16 halves wire bytes)

    def __post_init__(self):
        # Bridge both configuration routes: a bare EulerConfig becomes a
        # uniform policy; a NumericsContext back-fills ecfg for legacy
        # readers (e.g. code branching on ctx.ecfg.mode).
        if self.numerics is None:
            self.numerics = NumericsContext.from_ecfg(
                self.ecfg if self.ecfg is not None
                else EulerConfig(mode="exact"))
        if self.ecfg is None:
            self.ecfg = self.numerics.policy.default

    def shard(self, x, *spec):
        if self.mesh is None:
            return x
        axes = set(self.mesh.axis_names)
        clean = tuple(
            (tuple(a for a in s if a in axes) or None) if isinstance(s, tuple)
            else (s if (s is None or s in axes) else None)
            for s in spec)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, PS(*clean)))

    @property
    def batch_spec(self):
        return tuple(a for a in self.data_axes
                     if self.mesh is not None and a in self.mesh.axis_names) or None


def dot(a, b, ctx: Ctx, dn=None, op: str = "matmul"):
    """Policy-resolved dot_general; default contracts a's last with b's
    first dim (op kind "matmul")."""
    if dn is None:
        dn = (((a.ndim - 1,), (0,)), ((), ()))
    return N.dot_general(a, b, dn, ctx.numerics, op=op)


# --------------------------------------------------------------------------
# Primitives
# --------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, scale: float | None = None):
    scale = scale if scale is not None else d_in ** -0.5
    return {"w": jax.random.normal(key, (d_in, d_out), jnp.float32) * scale}


def dense_apply(p, x, ctx: Ctx):
    return dot(x, p["w"], ctx)


def is_held_weight(path) -> bool:
    """Whether a params-tree path (``tree_map_with_path`` keys) names a
    weight that a contraction reads whole, ``.../<name>/w``: every
    ``dense_init`` weight and the MoE block's expert stacks (contracted
    per expert over their ``[d_in, d_out]`` matrices), but not the MoE
    router, which runs in exact f32 (``moe_apply``)."""
    keys = [getattr(k, "key", None) for k in path]
    return (len(keys) >= 2 and keys[-1] == "w"
            and not (len(keys) >= 3 and keys[-3:-1] == ["moe", "router"]))


def rmsnorm_init(d: int):
    return {"g": jnp.ones((d,), jnp.float32)}


def rmsnorm_apply(p, x, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, -1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * p["g"]).astype(x.dtype)


def embed_init(key, vocab_p: int, d: int):
    return {"e": jax.random.normal(key, (vocab_p, d), jnp.float32) * 0.02}


def embed_apply(p, ids):
    return jnp.take(p["e"], ids, axis=0)


def rope(x, positions, theta: float, inv_freq=None, scale=None):
    """Rotary embedding on the last dim of x: [..., T, H, hd].  Plain RoPE
    at ``theta`` unless ``inv_freq`` ([hd/2]) is given; ``scale``
    multiplies cos and sin (YaRN's attention factor)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = (jnp.exp(-jnp.log(theta) * jnp.arange(half, dtype=jnp.float32)
                     / half) if inv_freq is None else inv_freq)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # [..., T, half]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    if scale is not None:
        cos, sin = cos * scale, sin * scale
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


def yarn_inv_freq(cfg) -> np.ndarray:
    """YaRN's rotary frequencies [hd/2] (float32), as Hugging Face
    ``transformers`` computes them (``_compute_yarn_parameters``), with
    ``dim = head_dim``, ``base = rope_theta``, ``s = yarn_factor`` and
    ``L = yarn_original_max_pos``:

        pos_i   = base ** (2 i / dim),                 i = 0 .. dim/2 - 1
        corr(r) = dim * ln(L / (2 pi r)) / (2 ln base)
        low     = max(floor(corr(beta_fast)), 0)
        high    = min(ceil(corr(beta_slow)), dim - 1)
        ramp_i  = clip((i - low) / (high - low), 0, 1)  (high += 0.001 if equal)
        inv_i   = (1 / (s pos_i)) ramp_i + (1 / pos_i) (1 - ramp_i)

    so the fast dimensions (i < low) keep their frequency and the slow
    ones (i > high) are interpolated by ``s``.  ``rope`` then multiplies
    cos and sin by ``yarn_attention_factor``."""
    dim, base = cfg.head_dim, cfg.rope_theta
    L = cfg.yarn_original_max_pos

    def corr(rot):
        return dim * math.log(L / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(corr(cfg.yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    pos = base ** (np.arange(0, dim, 2, dtype=np.float32) / np.float32(dim))
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    return (1 / (cfg.yarn_factor * pos) * ramp
            + 1 / pos * (1 - ramp)).astype(np.float32)


def rope_tables(cfg, window):
    """(inv_freq, scale) for ``rope`` on a layer of this ``window``: plain
    RoPE (None, None) unless the config has YaRN, which the full-attention
    layers take (window None or < 0; a traced window picks per layer, as
    the layer scan carries it) while window layers keep plain RoPE."""
    if not cfg.yarn_factor:
        return None, None
    yarn = jnp.asarray(yarn_inv_freq(cfg))
    att = jnp.float32(cfg.yarn_attention_factor)
    if window is None:
        return yarn, att
    half = cfg.head_dim // 2
    plain = jnp.exp(-jnp.log(cfg.rope_theta)
                    * jnp.arange(half, dtype=jnp.float32) / half)
    full = jnp.asarray(window, jnp.int32) < 0
    return jnp.where(full, yarn, plain), jnp.where(full, att, 1.0)


def _softcap(x, cap):
    return cap * jnp.tanh(x / cap) if cap else x


# --------------------------------------------------------------------------
# Attention (GQA, optional sliding window, softcaps, chunked-flash softmax)
# --------------------------------------------------------------------------

def attention_init(key, cfg):
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, H * hd),
        "wk": dense_init(ks[1], d, KV * hd),
        "wv": dense_init(ks[2], d, KV * hd),
        "wo": dense_init(ks[3], H * hd, d),
    }
    if cfg.qk_norm:
        p["qn"] = rmsnorm_init(cfg.head_dim)
        p["kn"] = rmsnorm_init(cfg.head_dim)
    return p


def _attn_scores(q, k, ctx: Ctx, softcap):
    # q: [B, T, H, hd], k: [B, S, KV, hd] (grouped) -> scores [B, H, T, S]
    B, T, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    group = H // KV
    qg = q.reshape(B, T, KV, group, hd)
    dn = (((4,), (3,)), ((0, 2), (0, 2)))  # contract hd; batch B, KV
    s = N.dot_general(qg, k, dn, ctx.numerics, op="qk")  # [B,KV,T,group,S]
    s = s * (hd ** -0.5)
    s = _softcap(s.astype(jnp.float32), softcap)
    return s  # [B, KV, T, group, S]


def _attn_values(p, v, ctx: Ctx):
    # p: [B, KV, T, group, S], v: [B, S, KV, hd] -> [B, T, KV*group*hd]
    dn = (((4,), (1,)), ((0, 1), (0, 2)))
    o = N.dot_general(p, v, dn, ctx.numerics, op="pv")  # [B,KV,T,group,hd]
    B, KV, T, group, hd = o.shape
    return jnp.moveaxis(o, 1, 2).reshape(B, T, KV * group * hd)


def causal_window_mask(t_pos, s_pos, window):
    """Causal + sliding-window mask.  ``window`` may be a *traced* int32
    scalar: window < 0 means global (no window) — this is what lets a single
    ``lax.scan`` over layers serve alternating local/global stacks."""
    m = s_pos[None, :] <= t_pos[:, None]
    if window is None:
        return m
    w = jnp.asarray(window, jnp.int32)
    win_ok = (w < 0) | (s_pos[None, :] > (t_pos[:, None] - w))
    return m & win_ok


def _maybe_qk_norm(p, q, k):
    if "qn" in p:
        q = rmsnorm_apply(p["qn"], q)
        k = rmsnorm_apply(p["kn"], k)
    return q, k


@N.scoped("attn")
def attention_apply(p, x, ctx: Ctx, cfg, window, positions,
                    cache=None, q_chunk: int = 1024, kv_chunk: int = 1024):
    """Full attention layer.

    Modes (selected statically from shapes):
      * cache is None            — training forward over x[B, T, d];
      * cache given and T > 1    — prefill: flash attention + KV slab write;
      * cache given and T == 1   — single-token decode at ctx.decode_pos.
    ``window``: python int, None, or traced int32 scalar (<0 = global).
    """
    B, T, d = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    if ctx.attn_head_shard and ctx.mesh is not None and T > 1:
        # Megatron SP entry: gather the sequence-sharded residual ONCE
        # (activations, bf16) so GSPMD stops replicating the TP-sharded
        # qkv WEIGHTS (f32, bigger) to resolve the T/model conflict.
        x = ctx.shard(x, ctx.data_axes, None, None)

    q = dense_apply(p["wq"], x, ctx).reshape(B, T, H, hd)
    k = dense_apply(p["wk"], x, ctx).reshape(B, T, KV, hd)
    v = dense_apply(p["wv"], x, ctx).reshape(B, T, KV, hd)
    q, k = _maybe_qk_norm(p, q, k)
    rot = rope_tables(cfg, window)
    q = rope(q, positions, cfg.rope_theta, *rot)
    k = rope(k, positions, cfg.rope_theta, *rot)

    if ctx.attn_head_shard and ctx.mesh is not None and T > 1:
        # Megatron attention: heads over `model`; each shard holds its heads
        # for the FULL sequence, so flash needs no per-layer T all-gather.
        msz = (ctx.mesh.shape[ctx.model_axis]
               if ctx.model_axis in ctx.mesh.axis_names else 1)
        if H % msz == 0 and KV % msz == 0:
            q = ctx.shard(q, ctx.data_axes, None, ctx.model_axis, None)
            k = ctx.shard(k, ctx.data_axes, None, ctx.model_axis, None)
            v = ctx.shard(v, ctx.data_axes, None, ctx.model_axis, None)

    if cache is not None and T == 1 and ctx.page_table is not None:
        # ---- paged decode ----
        # The cache is the shared page pool [P, page_size, KV, hd]; this
        # slot's token goes to the physical page its page table names for
        # the current logical page.  Masked rows (retired slots) and rows
        # whose table entry is unallocated redirect to the TRASH_PAGE
        # write sink, so the store stays a plain scatter.  Attention then
        # dispatches whole through the numerics registry (gather +
        # softmax + qk/pv), where the pallas backend may run the fused
        # flash-decode kernel.
        from repro.kernels.paged_decode import NULL_PAGE, TRASH_PAGE
        kp, vp = cache["k"], cache["v"]
        pc = cache_policy_pc(ctx, kp.dtype)
        pos = jnp.asarray(ctx.decode_pos, jnp.int32)
        pos_b = jnp.full((B,), pos) if pos.ndim == 0 else pos  # [B]
        ps_ = kp.shape[1]
        nlp = ctx.page_table.shape[1]
        lp = jnp.clip(pos_b // ps_, 0, nlp - 1)
        off = pos_b % ps_
        phys = jnp.take_along_axis(ctx.page_table, lp[:, None], 1)[:, 0]
        phys = jnp.where(phys == NULL_PAGE, TRASH_PAGE, phys)
        if ctx.decode_write is not None:
            phys = jnp.where(jnp.asarray(ctx.decode_write, bool),
                             phys, TRASH_PAGE)
        kp = kp.at[phys, off].set(cache_encode(k[:, 0], kp.dtype, pc))
        vp = vp.at[phys, off].set(cache_encode(v[:, 0], vp.dtype, pc))
        out = N.decode_attention(q, kp, vp, ctx.page_table, pos_b,
                                 ctx.numerics, pc=pc,
                                 softcap=cfg.attn_softcap, window=window)
        y = dense_apply(p["wo"], out.astype(x.dtype), ctx)
        return y, {"k": kp, "v": vp}

    if cache is not None and T == 1:
        # ---- decode ----
        # ``ctx.decode_pos`` is a scalar (whole batch at one position) or a
        # [B] vector (continuous batching: every slot at its own position).
        # Both are normalized to per-row positions so cache writes and
        # validity masks are per-slot.
        ck, cv = cache["k"], cache["v"]
        pc = cache_policy_pc(ctx, ck.dtype)
        pos = jnp.asarray(ctx.decode_pos, jnp.int32)
        pos_b = jnp.full((B,), pos) if pos.ndim == 0 else pos  # [B]

        def _row_write(c, u, p_row):
            return jax.lax.dynamic_update_slice(c, u, (p_row, 0, 0))

        ck = jax.vmap(_row_write)(ck, cache_encode(k, ck.dtype, pc), pos_b)
        cv = jax.vmap(_row_write)(cv, cache_encode(v, cv.dtype, pc), pos_b)
        S = ck.shape[1]
        s_pos = jnp.arange(S)
        kd = cache_decode(ck, x.dtype, pc)
        vd = cache_decode(cv, x.dtype, pc)
        scores = _attn_scores(q, kd, ctx, cfg.attn_softcap)  # [B,KV,1,g,S]
        valid = s_pos[None, :] <= pos_b[:, None]             # [B, S]
        if window is not None:
            w = jnp.asarray(window, jnp.int32)
            valid &= (w < 0) | (s_pos[None, :] > pos_b[:, None] - w)
        scores = jnp.where(valid[:, None, None, None, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(vd.dtype)
        out = _attn_values(probs, vd, ctx)
        y = dense_apply(p["wo"], out.astype(x.dtype), ctx)
        return y, {"k": ck, "v": cv}

    # ---- train / prefill: chunked (flash-style) causal attention ----
    # chunk sizes must divide T; paged admission pads prompts to arbitrary
    # page multiples, so fall back to the largest divisor <= the configured
    # chunk (identical to min(chunk, T) whenever that already divides T)
    qc = min(q_chunk, T)
    while T % qc:
        qc -= 1
    kc = min(kv_chunk, T)
    while T % kc:
        kc -= 1
    n_q, n_k = T // qc, T // kc
    group = H // KV

    def q_block(qi):
        q_i = jax.lax.dynamic_slice_in_dim(q, qi * qc, qc, 1)
        t_idx = jnp.arange(qc) + qi * qc

        m0 = jnp.full((B, KV, qc, group), -1e30, jnp.float32)
        l0 = jnp.zeros((B, KV, qc, group), jnp.float32)
        a0 = jnp.zeros((B, KV, qc, group, hd), jnp.float32)

        def step(carry, ki):
            m_run, l_run, acc = carry
            k_i = jax.lax.dynamic_slice_in_dim(k, ki * kc, kc, 1)
            v_i = jax.lax.dynamic_slice_in_dim(v, ki * kc, kc, 1)
            s = _attn_scores(q_i, k_i, ctx, cfg.attn_softcap)  # [B,KV,qc,g,kc]
            s_idx = jnp.arange(kc) + ki * kc
            mask = causal_window_mask(t_idx, s_idx, window)
            s = jnp.where(mask[None, None, :, None, :], s, -1e30)
            m_new = jnp.maximum(m_run, s.max(-1))
            alpha = jnp.exp(m_run - m_new)
            pexp = jnp.exp(s - m_new[..., None])
            l_new = l_run * alpha + pexp.sum(-1)
            dn = (((4,), (1,)), ((0, 1), (0, 2)))
            o = N.dot_general(pexp.astype(v_i.dtype), v_i, dn, ctx.numerics,
                              op="pv")
            acc = acc * alpha[..., None] + o
            return (m_new, l_new, acc), None

        # remat each K/V step: backward recomputes the [.., qc, kc] score
        # block instead of saving it — the flash-attention memory contract
        step = jax.checkpoint(step, prevent_cse=False)
        with jax.named_scope("attn_kv"):
            (m_f, l_f, acc), _ = jax.lax.scan(step, (m0, l0, a0),
                                              jnp.arange(n_k))
        out = acc / jnp.maximum(l_f[..., None], 1e-30)      # [B,KV,qc,g,hd]
        return jnp.moveaxis(out, 2, 1).reshape(B, qc, H * hd)

    outs = [q_block(i) for i in range(n_q)]
    out = jnp.concatenate(outs, 1) if len(outs) > 1 else outs[0]
    y = dense_apply(p["wo"], out.astype(x.dtype), ctx)

    new_cache = None
    if cache is not None:  # prefill: write the K/V slab at offset 0
        pc = cache_policy_pc(ctx, cache["k"].dtype)
        ck = jax.lax.dynamic_update_slice(
            cache["k"], cache_encode(k, cache["k"].dtype, pc), (0, 0, 0, 0))
        cv = jax.lax.dynamic_update_slice(
            cache["v"], cache_encode(v, cache["v"].dtype, pc), (0, 0, 0, 0))
        new_cache = {"k": ck, "v": cv}
    return y, new_cache


def attention_cache_init(cfg, batch: int, max_len: int, dtype=jnp.float32):
    return {"k": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.head_dim), dtype),
            "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.head_dim), dtype)}


def cache_reset(cache, slot=None, batch_axis: int = 0):
    """Explicit cache lifecycle: zero a cache pytree.

    ``slot=None`` invalidates the whole cache; an integer (or traced int32)
    ``slot`` zeroes one batch row only — the primitive the serving layer
    uses to invalidate a slot so no KV/SSM state can leak between
    requests.  ``batch_axis`` is 0 for the unstacked per-layer caches and
    1 for the model-level [L, B, ...] stacks.  uint8 posit KV caches zero
    to the Posit(8,0) zero pattern, which is the 0 byte.
    """
    if slot is None:
        return jax.tree.map(jnp.zeros_like, cache)
    slot = jnp.asarray(slot, jnp.int32)

    def _zero_row(a):
        shape = a.shape[:batch_axis] + (1,) + a.shape[batch_axis + 1:]
        return jax.lax.dynamic_update_slice_in_dim(
            a, jnp.zeros(shape, a.dtype), slot, axis=batch_axis)

    return jax.tree.map(_zero_row, cache)


# --------------------------------------------------------------------------
# MLP variants
# --------------------------------------------------------------------------

def mlp_init(key, cfg, d_ff=None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp in ("silu_gated", "gelu_gated"):
        return {"wi": dense_init(ks[0], d, f), "wg": dense_init(ks[1], d, f),
                "wo": dense_init(ks[2], f, d)}
    return {"wi": dense_init(ks[0], d, f), "wo": dense_init(ks[2], f, d)}


@N.scoped("mlp")
def mlp_apply(p, x, ctx: Ctx, kind: str):
    h = dense_apply(p["wi"], x, ctx)
    if kind == "silu_gated":
        h = jax.nn.silu(dense_apply(p["wg"], x, ctx)) * h
    elif kind == "gelu_gated":
        h = jax.nn.gelu(dense_apply(p["wg"], x, ctx), approximate=True) * h
    elif kind == "relu2":  # squared ReLU (nemotron)
        r = jax.nn.relu(h)
        h = r * r
    elif kind == "gelu":
        h = jax.nn.gelu(h, approximate=True)
    else:
        raise ValueError(kind)
    return dense_apply(p["wo"], h, ctx)


# --------------------------------------------------------------------------
# Mixture of Experts (top-k router, sort-free dispatch, EP-shardable)
# --------------------------------------------------------------------------

def moe_init(key, cfg):
    """The router has all ``cfg.n_routed`` outputs; the expert stacks hold
    this device's ``cfg.n_experts``."""
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], d, cfg.n_routed, scale=0.02),
        "wi": {"w": jax.random.normal(ks[1], (E, d, f), jnp.float32) * d ** -0.5},
        "wg": {"w": jax.random.normal(ks[2], (E, d, f), jnp.float32) * d ** -0.5},
        "wo": {"w": jax.random.normal(ks[3], (E, f, d), jnp.float32) * f ** -0.5},
    }
    if cfg.moe_dense_residual:
        p["dense"] = mlp_init(ks[4], cfg)
    return p


def _moe_expert_block(xl, il, gl, wi, wg, wo, *, e0, E_local: int, cap,
                      nctx, gather_axes=None, gather_dtype=None):
    """Per-device expert block: dispatch my tokens to MY experts, run the
    expert FFN, combine back to token order.  Used both as the single-device
    path (e0 = the first expert held) and as the shard_map body
    (e0=axis_index*E_local, partial output later psum'd over ``model``).

    xl [n, d] local tokens; il/gl [n, k] router choices/gates (f32);
    wi/wg [E_local, d, f*]; wo [E_local, f*, d].  With ``gather_axes`` the
    weights' f dim is ZeRO-3 storage-sharded and explicitly all-gathered
    here (transient, per layer).

    ``cap``: rows per expert, filled in token order; tokens past it are
    dropped (training's capacity).  ``cap=None`` is serving's drop-free
    dispatch with capacity n: row t of every expert is token t, zeros
    where the router did not send it there.  A token reaches an expert at
    most once, so none is dropped, a token's output does not depend on its
    neighbours, and the [E_local, n, d] buffers (so the contractions'
    grids and tile counts) depend on the row count n alone, never on the
    routing."""
    n, k = il.shape
    d = xl.shape[-1]
    if cap is None:
        hit = (il - e0)[None] == jnp.arange(E_local)[:, None, None]
        buf = jnp.where(hit.any(-1)[..., None], xl[None], 0).astype(xl.dtype)
        combine = jnp.sum(jnp.where(hit, gl[None], 0.0), -1)  # [E_l, n]
    else:
        gl = gl.astype(xl.dtype)
        flat_e = il.reshape(-1) - e0                           # local expert id
        mine = (flat_e >= 0) & (flat_e < E_local)
        safe_e = jnp.where(mine, flat_e, E_local)              # junk bucket
        onehot = jax.nn.one_hot(safe_e, E_local + 1, dtype=jnp.int32)
        rank = (jnp.cumsum(onehot, 0) - 1)[jnp.arange(n * k), safe_e]
        keep = mine & (rank < cap)
        tok_idx = jnp.repeat(jnp.arange(n), k)
        buf = jnp.zeros((E_local, cap, d), xl.dtype)
        buf = buf.at[jnp.where(keep, flat_e, E_local - 1),
                     jnp.where(keep, rank, cap - 1)].add(
            jnp.where(keep[:, None], xl[tok_idx], 0.0).astype(xl.dtype))

    if gather_axes:  # ZeRO-3: materialize my experts' full f dim, per layer
        if gather_dtype is not None:
            # cast BEFORE the gather so the wire carries bf16.  The barrier
            # sits AFTER the gather: without it XLA hoists the codec's f32
            # up-convert across the collective (merging it with this
            # down-convert), silently re-widening the wire to f32.
            wi = wi.astype(gather_dtype)
            wg = wg.astype(gather_dtype)
            wo = wo.astype(gather_dtype)
        wi = jax.lax.all_gather(wi, gather_axes, axis=2, tiled=True)
        wg = jax.lax.all_gather(wg, gather_axes, axis=2, tiled=True)
        wo = jax.lax.all_gather(wo, gather_axes, axis=1, tiled=True)
        if gather_dtype is not None:
            wi, wg, wo = jax.lax.optimization_barrier((wi, wg, wo))

    dnb = (((2,), (1,)), ((0,), (0,)))
    h = N.dot_general(buf, wi, dnb, nctx, op="matmul")
    g = N.dot_general(buf, wg, dnb, nctx, op="matmul")
    h = jax.nn.silu(g) * h
    out = N.dot_general(h, wo, dnb, nctx, op="matmul")         # [E_l, cap, d]

    if cap is None:
        return jnp.sum(out.astype(jnp.float32) * combine[..., None], 0)
    gathered = out[jnp.where(keep, flat_e, 0), jnp.where(keep, rank, 0)]
    gathered = jnp.where(keep[:, None], gathered, 0.0)
    y = jnp.zeros((n, d), gathered.dtype)
    return y.at[tok_idx].add(gathered * gl.reshape(-1)[:, None])


@N.scoped("moe")
def moe_apply(p, x, ctx: Ctx, cfg, drop_free: bool = False):
    """Top-k MoE over the experts this device holds.

    The router runs in exact f32 over all ``cfg.n_routed`` experts (the
    paper's exact control path), takes each token's top-k of the softmax
    and renormalizes those k weights to sum to 1.  The layer holds experts
    ``[cfg.expert_offset, cfg.expert_offset + cfg.n_experts)`` and returns
    their part of the output: what the other experts add comes from the
    devices that hold them, and is left out here.

    ``drop_free`` (serving's prefill and decode) gives every held expert
    one row per token (``_moe_expert_block`` with ``cap=None``); training
    keeps ``capacity_factor`` rows per expert.

    With a mesh (training, every expert held): one ``shard_map`` over the
    whole mesh runs dispatch -> expert FFN -> combine per device: tokens
    stay sharded over (pod, data) with PER-DEVICE capacity; each ``model``
    shard handles its E/model experts and the partial token outputs are
    psum'd over ``model``.  With ``ctx.moe_fsdp`` (arctic) expert weights
    are additionally ZeRO-3 storage-sharded over data and all-gathered
    transiently inside the block.  Token-space and expert-space tensors
    never materialize globally.

    Returns (y, aux loss, counts): counts ``expert_rows``, the token-expert
    pairs routed to held experts, and ``expert_slots``, the rows the held
    experts computed (int32)."""
    B, T, d = x.shape
    E, k, E_held = cfg.n_routed, cfg.top_k, cfg.n_experts
    e0 = cfg.expert_offset
    n_tok = B * T
    xt = x.reshape(n_tok, d)

    logits = jnp.matmul(xt.astype(jnp.float32),
                        p["router"]["w"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    gates, ids = jax.lax.top_k(jax.nn.softmax(logits, -1), k)   # [n, k]
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    mesh = ctx.mesh
    da = (tuple(a for a in ctx.data_axes if a in mesh.axis_names)
          if mesh is not None else ())
    dp = int(np.prod([mesh.shape[a] for a in da])) if da else 1
    msz = (mesh.shape[ctx.model_axis]
           if mesh is not None and ctx.model_axis in mesh.axis_names else 1)
    use_smap = (mesh is not None and (dp > 1 or msz > 1)
                and n_tok % dp == 0 and E % msz == 0 and E == E_held)
    cap = (None if drop_free
           else int(max(1, round(n_tok / dp * k / E * cfg.capacity_factor))))

    if use_smap:
        from jax.sharding import PartitionSpec as _P
        E_local = E // msz
        fsdp = ctx.moe_fsdp and dp > 1
        ma = ctx.model_axis

        def body(xl, il, gl, wi, wg, wo):
            e0 = (jax.lax.axis_index(ma) * E_local) if msz > 1 else 0
            y = _moe_expert_block(
                xl, il, gl, wi, wg, wo, e0=e0, E_local=E_local, cap=cap,
                nctx=ctx.numerics, gather_axes=da if fsdp else None,
                gather_dtype=ctx.moe_gather_dtype)
            if msz > 1:
                y = jax.lax.psum(y, ma)
            return y

        f_sh = da if fsdp else None
        y = jax.shard_map(
            body, mesh=mesh,
            in_specs=(_P(da or None, None), _P(da or None, None),
                      _P(da or None, None),
                      _P(ma, None, f_sh), _P(ma, None, f_sh),
                      _P(ma, f_sh, None)),
            out_specs=_P(da or None, None), check_vma=False,
        )(xt, ids, gates, p["wi"]["w"], p["wg"]["w"], p["wo"]["w"])
        per_expert = n_tok if cap is None else cap * dp
    else:
        y = _moe_expert_block(xt, ids, gates, p["wi"]["w"], p["wg"]["w"],
                              p["wo"]["w"], e0=e0, E_local=E_held, cap=cap,
                              nctx=ctx.numerics)
        per_expert = n_tok if cap is None else cap

    if cfg.moe_dense_residual:
        y = y + mlp_apply(p["dense"], xt, ctx, "silu_gated")
    # router aux loss (load balancing, Switch-style)
    me = jnp.mean(jax.nn.softmax(logits, -1), 0)
    ce = jnp.mean(jax.nn.one_hot(ids[:, 0], E, dtype=jnp.float32), 0)
    aux = E * jnp.sum(me * ce)
    counts = {"expert_rows": jnp.sum((ids >= e0) & (ids < e0 + E_held),
                                     dtype=jnp.int32),
              "expert_slots": jnp.int32(E_held * per_expert)}
    return y.astype(x.dtype).reshape(B, T, d), aux, counts
