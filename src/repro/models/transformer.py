"""Decoder-only backbone composing the layer zoo, with EULER-ADAS numerics.

One ``Model`` class serves all six assigned families:

  dense / audio / vlm : attention + MLP blocks (audio/vlm differ only in the
                        stubbed modality frontend — ``embedding_inputs``)
  moe                 : attention + MoE blocks (optional dense residual)
  ssm                 : Mamba-2 SSD blocks (attention-free)
  hybrid              : parallel attention + SSD heads per block (hymba)

Scale features:
  * ``scan_layers`` — layers are stacked pytrees scanned with ``lax.scan``
    (MaxText-style); keeps HLO size O(1) in depth, essential for the 46-layer
    dry-runs.  Per-layer heterogeneity (local/global windows) is expressed as
    *traced* per-layer scalars so one scan body serves all layers.
  * chunked cross-entropy — logits are never materialized at [B, T, V];
    the loss scans over T-chunks re-computing one [B, tc, V] slab at a time
    (remat'd), which is what makes vocab=256k trainable.
  * remat — each block is wrapped in ``jax.checkpoint`` (policy configurable).
  * caches — stacked [L, ...] KV / SSM-state caches with static-shape
    prefill/decode steps (T>1 → prefill, T==1 → decode).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro import numerics as N
from repro.core.engine import EulerConfig
from repro.numerics import NumericsContext, stored

from . import layers as L
from . import ssm as S
from .config import ModelConfig
from .layers import Ctx

_REMAT_POLICIES = {
    "none": None,
    "dots": "dots_with_no_batch_dims_saveable",
    "nothing": "nothing_saveable",
    "everything": "everything_saveable",
}


def _no_counts():
    """Expert counts of a layer without experts (``layers.moe_apply``)."""
    return {"expert_rows": jnp.int32(0), "expert_slots": jnp.int32(0)}


def _policy(name):
    key = _REMAT_POLICIES[name]
    return getattr(jax.checkpoint_policies, key) if key else None


class Model:
    """init / loss / prefill / decode_step for one ModelConfig."""

    def __init__(self, cfg: ModelConfig, ecfg: EulerConfig | None = None,
                 remat: bool = True, remat_policy: str = "nothing",
                 numerics: NumericsContext | None = None):
        self.cfg = cfg
        if numerics is None:
            numerics = NumericsContext.from_ecfg(
                ecfg or EulerConfig(mode="exact"))
        self.numerics = numerics
        self.ecfg = ecfg or numerics.policy.default
        self.remat = remat
        self.remat_policy = remat_policy
        self.compute_dtype = jnp.dtype(cfg.dtype)

    def make_ctx(self, **kw) -> Ctx:
        """A Ctx pre-wired with this model's numerics (mesh etc. via kw)."""
        return Ctx(ecfg=self.ecfg, numerics=self.numerics, **kw)

    # ------------------------------------------------------------------
    # Parameter init
    # ------------------------------------------------------------------

    def _block_init(self, key):
        cfg = self.cfg
        ks = jax.random.split(key, 8)
        p: dict[str, Any] = {"ln1": L.rmsnorm_init(cfg.d_model)}
        fam = cfg.family
        if fam in ("dense", "audio", "vlm", "moe", "hybrid"):
            p["attn"] = L.attention_init(ks[0], cfg)
            if cfg.post_norm:
                p["pn1"] = L.rmsnorm_init(cfg.d_model)
        if fam in ("dense", "audio", "vlm", "hybrid"):
            p["ln2"] = L.rmsnorm_init(cfg.d_model)
            p["mlp"] = L.mlp_init(ks[1], cfg)
            if cfg.post_norm:
                p["pn2"] = L.rmsnorm_init(cfg.d_model)
        if fam == "moe":
            p["ln2"] = L.rmsnorm_init(cfg.d_model)
            p["moe"] = L.moe_init(ks[2], cfg)
        if fam == "ssm":
            p["ssm"] = S.ssm_init(ks[3], cfg)
        if fam == "hybrid":
            p["ssm"] = S.ssm_init(ks[3], cfg)
            p["bn_a"] = L.rmsnorm_init(cfg.d_model)
            p["bn_s"] = L.rmsnorm_init(cfg.d_model)
        return p

    def init(self, key):
        cfg = self.cfg
        k_emb, k_layers = jax.random.split(key)
        layer_keys = jax.random.split(k_layers, cfg.n_layers)
        layers = jax.vmap(self._block_init)(layer_keys)
        params = {
            "embed": L.embed_init(k_emb, cfg.vocab_padded, cfg.d_model),
            "layers": layers,
            "ln_f": L.rmsnorm_init(cfg.d_model),
        }
        return params

    def hold_weights(self, params, pc):
        """``params`` with every weight a contraction reads whole held as
        posit words of format ``pc`` (``numerics.stored.hold``): each
        ``dense_apply`` weight and each expert's matrices (contracted over
        axis 0 of the matrix, one scale per matrix), and the tied head,
        added at ``"head"`` (the embedding contracted over axis 1 after the
        cast to the compute dtype).  The float leaves stay: the embedding
        gather and the exact-f32 MoE router read them."""
        def one(path, leaf):
            return stored.hold(leaf, pc) if L.is_held_weight(path) else leaf
        out = dict(params)
        out["layers"] = jax.tree_util.tree_map_with_path(one,
                                                         params["layers"])
        out["head"] = stored.hold(params["embed"]["e"], pc, axis=1,
                                  dtype=self.compute_dtype)
        return out

    def param_count(self, params) -> int:
        return sum(int(x.size) for x in jax.tree.leaves(params))

    # ------------------------------------------------------------------
    # Per-layer windows (traced through the scan)
    # ------------------------------------------------------------------

    def layer_windows(self):
        cfg = self.cfg
        wins = []
        for i in range(cfg.n_layers):
            kind = cfg.layer_kind(i)
            wins.append(cfg.window if (kind == "local" and cfg.window) else -1)
        return jnp.asarray(wins, jnp.int32)

    # ------------------------------------------------------------------
    # One block
    # ------------------------------------------------------------------

    def _block(self, p, x, ctx: Ctx, window, positions, cache):
        """One layer: (x, new cache, aux loss, expert counts)."""
        cfg = self.cfg
        fam = cfg.family
        aux = jnp.float32(0.0)
        counts = _no_counts()
        new_cache = cache

        if fam == "ssm":
            h, sc = S.ssm_apply(p["ssm"], L.rmsnorm_apply(p["ln1"], x), ctx,
                                cfg, cache)
            x = x + h.astype(x.dtype)
            return x, sc, aux, counts

        if fam == "hybrid":
            xin = L.rmsnorm_apply(p["ln1"], x)
            a_cache = s_cache = None
            if cache is not None:
                a_cache = {"k": cache["k"], "v": cache["v"]}
                s_cache = {"state": cache["state"], "conv": cache["conv"]}
            ha, ac = L.attention_apply(p["attn"], xin, ctx, cfg, window,
                                       positions, a_cache,
                                       q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk)
            hs, sc = S.ssm_apply(p["ssm"], xin, ctx, cfg, s_cache)
            # hymba-style fusion: per-branch normalization then mean
            h = 0.5 * (L.rmsnorm_apply(p["bn_a"], ha) +
                       L.rmsnorm_apply(p["bn_s"], hs))
            x = x + h.astype(x.dtype)
            x = x + L.mlp_apply(p["mlp"], L.rmsnorm_apply(p["ln2"], x), ctx,
                                cfg.mlp).astype(x.dtype)
            if cache is not None:
                new_cache = {"k": ac["k"], "v": ac["v"],
                             "state": sc["state"], "conv": sc["conv"]}
            return x, new_cache, aux, counts

        # attention families: dense / audio / vlm / moe
        h, ac = L.attention_apply(p["attn"], L.rmsnorm_apply(p["ln1"], x), ctx,
                                  cfg, window, positions, cache,
                                  q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk)
        if cfg.post_norm:
            h = L.rmsnorm_apply(p["pn1"], h)
        x = x + h.astype(x.dtype)
        xin = L.rmsnorm_apply(p["ln2"], x)
        if fam == "moe":
            # serving (a cache: prefill and decode) routes drop-free
            h, aux, counts = L.moe_apply(p["moe"], xin, ctx, cfg,
                                         drop_free=cache is not None)
        else:
            h = L.mlp_apply(p["mlp"], xin, ctx, cfg.mlp)
        if cfg.post_norm:
            h = L.rmsnorm_apply(p["pn2"], h)
        x = x + h.astype(x.dtype)
        return x, ac, aux, counts

    # ------------------------------------------------------------------
    # Stack forward
    # ------------------------------------------------------------------

    def forward(self, params, inputs, ctx: Ctx, cache=None, positions=None):
        """inputs: int token ids [B, T] or float embeddings [B, T, d].
        Returns (hidden [B, T, d], new_cache, aux)."""
        return self._forward(params, inputs, ctx, cache, positions)[:3]

    def _forward(self, params, inputs, ctx: Ctx, cache, positions):
        """``forward``, and the expert counts summed over the layers."""
        cfg = self.cfg
        if jnp.issubdtype(jnp.asarray(inputs).dtype, jnp.floating):
            x = inputs.astype(self.compute_dtype)
        else:
            x = L.embed_apply(params["embed"], inputs).astype(self.compute_dtype)
        B, T = x.shape[0], x.shape[1]
        if positions is None:
            if ctx.decode_pos is None:
                positions = jnp.arange(T, dtype=jnp.int32)
            else:
                # decode: scalar position (whole batch in lockstep) keeps the
                # [1]-shaped legacy layout; a [B] vector (continuous
                # batching, every slot at its own offset) becomes [B, 1] so
                # RoPE broadcasts per row.
                dp = jnp.asarray(ctx.decode_pos, jnp.int32)
                positions = dp.reshape(1) if dp.ndim == 0 else dp[:, None]
        x = ctx.shard(x, ctx.data_axes, None, None)

        windows = self.layer_windows()

        # Megatron-style sequence parallelism on the residual stream: the
        # per-layer carry is sharded [B/(dp), T/model, d], so the scan's saved
        # residual stack (the dominant training buffer) shrinks by the TP
        # degree.  GSPMD inserts the all-gather before qkv/in-proj and the
        # reduce-scatter after the row-sharded projections.
        def _sp(h):
            T = h.shape[1]
            if (ctx.mesh is not None and "model" in ctx.mesh.axis_names
                    and T > 1 and T % ctx.mesh.shape["model"] == 0):
                return ctx.shard(h, ctx.data_axes, "model", None)
            return h

        x = _sp(x)

        # close over ctx/positions (non-pytree) so jax.checkpoint only sees
        # array pytrees
        def block(p_l, h, win, c_l):
            y, c_new, a, n = self._block(p_l, h, ctx, win, positions, c_l)
            return _sp(y), c_new, a, n

        if self.remat:
            block = jax.checkpoint(
                block, policy=_policy(self.remat_policy), prevent_cse=False)

        add = functools.partial(jax.tree.map, jnp.add)
        carry0 = (jnp.float32(0.0), _no_counts())
        if cfg.scan_layers:
            if cache is None:
                def f(carry, xs):
                    h, aux, n = carry
                    p_l, win = xs
                    y, _, a, n_l = block(p_l, h, win, None)
                    return (y, aux + a, add(n, n_l)), None
                with jax.named_scope("layers"):
                    (x, aux, counts), _ = jax.lax.scan(
                        f, (x, *carry0), (params["layers"], windows))
                new_cache = None
            else:
                def f(carry, xs):
                    h, aux, n = carry
                    p_l, win, c_l = xs
                    y, c_new, a, n_l = block(p_l, h, win, c_l)
                    return (y, aux + a, add(n, n_l)), c_new
                with jax.named_scope("layers"):
                    (x, aux, counts), new_cache = jax.lax.scan(
                        f, (x, *carry0), (params["layers"], windows, cache))
        else:
            aux, counts = carry0
            new_caches = []
            for i in range(cfg.n_layers):
                p_l = jax.tree.map(lambda a: a[i], params["layers"])
                c_l = (None if cache is None
                       else jax.tree.map(lambda a: a[i], cache))
                # unscanned stacks get a per-layer path component, so
                # policies can pin precision by depth ("layer0/*", ...)
                with N.scope(f"layer{i}"):
                    x, c_new, a, n_l = block(p_l, x, windows[i], c_l)
                aux = aux + a
                counts = add(counts, n_l)
                new_caches.append(c_new)
            new_cache = (None if cache is None else
                         jax.tree.map(lambda *xs: jnp.stack(xs), *new_caches))

        x = L.rmsnorm_apply(params["ln_f"], x)
        return x, new_cache, aux, counts

    # ------------------------------------------------------------------
    # Output head + loss
    # ------------------------------------------------------------------

    def head(self, params, h, ctx: Ctx):
        """hidden [..., d] -> logits [..., vocab_padded] (tied embeddings).
        A tree from :meth:`hold_weights` holds the head's words at
        ``params["head"]``."""
        cfg = self.cfg
        emb = params.get("head")
        if emb is None:
            emb = params["embed"]["e"].astype(h.dtype)
        dn = (((h.ndim - 1,), (1,)), ((), ()))
        with N.scope("head"):
            logits = N.dot_general(h, emb, dn, ctx.numerics,
                                   op="matmul").astype(jnp.float32)
        if cfg.logit_softcap:
            logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
        if cfg.vocab_padded > cfg.vocab:  # mask padded vocab slots
            pad = jnp.arange(cfg.vocab_padded) >= cfg.vocab
            logits = jnp.where(pad, -1e30, logits)
        return logits

    def loss(self, params, batch, ctx: Ctx):
        """Mean next-token cross-entropy with T-chunked logits.

        batch: {"inputs": ids [B,T] or embeds [B,T,d], "labels": ids [B,T]}.
        Returns (loss, metrics dict)."""
        cfg = self.cfg
        hidden, _, aux = self.forward(params, batch["inputs"], ctx)
        labels = batch["labels"]
        B, T = labels.shape
        tc = min(cfg.loss_chunk, T)
        assert T % tc == 0
        nch = T // tc
        h = jnp.moveaxis(hidden.reshape(B, nch, tc, -1), 1, 0)   # [nch,B,tc,d]
        y = jnp.moveaxis(labels.reshape(B, nch, tc), 1, 0)       # [nch,B,tc]

        def chunk_loss(h_c, y_c):
            logits = self.head(params, h_c, ctx)                 # [B,tc,Vp]
            logz = jax.scipy.special.logsumexp(logits, -1)
            ll = jnp.take_along_axis(logits, y_c[..., None], -1)[..., 0]
            return jnp.sum(logz - ll)

        if self.remat:
            chunk_loss = jax.checkpoint(chunk_loss)

        def f(acc, xs):
            h_c, y_c = xs
            return acc + chunk_loss(h_c, y_c), None

        with jax.named_scope("loss_chunks"):
            total, _ = jax.lax.scan(f, jnp.float32(0.0), (h, y))
        loss = total / (B * T)
        if cfg.family == "moe":
            loss = loss + 0.01 * aux
        return loss, {"xent": total / (B * T), "aux": aux}

    # ------------------------------------------------------------------
    # Serving
    # ------------------------------------------------------------------

    def init_cache(self, batch: int, max_len: int, dtype=None):
        cfg = self.cfg
        dtype = dtype or jnp.dtype(cfg.cache_dtype)
        Ln = cfg.n_layers

        def stack(tree):
            return jax.tree.map(
                lambda a: jnp.broadcast_to(a, (Ln,) + a.shape).copy(), tree)

        fam = cfg.family
        fdt = jnp.bfloat16 if dtype == jnp.uint8 else dtype  # conv/state stay
        if fam == "ssm":                                     # floating point
            return stack(S.ssm_cache_init(cfg, batch, fdt))
        if fam == "hybrid":
            c = L.attention_cache_init(cfg, batch, max_len, dtype)
            c.update(S.ssm_cache_init(cfg, batch, fdt))
            return stack(c)
        return stack(L.attention_cache_init(cfg, batch, max_len, dtype))

    def init_paged_cache(self, num_pages: int, page_size: int, dtype=None):
        """Shared page pool: ``{"k","v"}`` of ``[L, P, page_size, KV, hd]``.

        Replaces the per-slot ``[L, B, max_len, ...]`` dense cache for
        serving decode: slots address the pool through page tables
        (``serving/kvcache.py``), so HBM scales with live tokens, not
        ``batch * max_len``.  Pages 0/1 are reserved (null read page /
        trash write sink) and must stay zero.  Attention-only layout —
        SSM/hybrid recurrent state has no sequence axis to page."""
        cfg = self.cfg
        if cfg.family in ("ssm", "hybrid"):
            raise ValueError(
                f"paged KV cache requires attention caches; family "
                f"{cfg.family!r} holds recurrent state")
        dtype = dtype or jnp.dtype(cfg.cache_dtype)
        shape = (cfg.n_layers, num_pages, page_size, cfg.n_kv_heads,
                 cfg.head_dim)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    def reset_cache(self, cache, slot=None):
        """Explicit cache lifecycle for serving.

        ``slot=None`` zeroes the whole cache (``reset_all``); an int /
        traced int32 zeroes one batch row (``reset_slot``) so a retired
        request's KV *and* recurrent SSM state cannot leak into the next
        occupant of the slot.  Model-level caches are [L, B, ...] stacks,
        hence ``batch_axis=1``."""
        return L.cache_reset(cache, slot, batch_axis=1)

    def prefill(self, params, inputs, ctx: Ctx, cache, *,
                with_counts: bool = False):
        """Run the prompt through the stack, filling the cache.
        Returns (last-position logits [B, Vp], cache), and with
        ``with_counts`` the expert counts summed over the layers
        (``layers.moe_apply``; zeros without experts)."""
        hidden, cache, _, counts = self._forward(params, inputs, ctx, cache,
                                                 None)
        logits = self.head(params, hidden[:, -1:, :], ctx)[:, 0, :]
        return (logits, cache, counts) if with_counts else (logits, cache)

    def decode_step(self, params, tok, pos, cache, ctx: Ctx, *,
                    page_table=None, write_mask=None,
                    with_counts: bool = False):
        """One decode step.  tok: [B] int32; pos: traced scalar position
        (lockstep batch) or [B] int32 vector (per-slot positions, used by
        the continuous-batching scheduler).  With ``page_table``
        ([B, n_logical] int32), ``cache`` is the shared page pool and
        attention runs the paged decode path; ``write_mask`` ([B] bool)
        redirects masked rows' cache writes to the trash page.  Returns
        (logits [B, Vp], new cache), and the expert counts with
        ``with_counts`` (as ``prefill``)."""
        ctx = dataclasses.replace(ctx, decode_pos=pos, page_table=page_table,
                                  decode_write=write_mask)
        hidden, cache, _, counts = self._forward(params, tok[:, None], ctx,
                                                 cache, None)
        logits = self.head(params, hidden[:, 0, :], ctx)
        return (logits, cache, counts) if with_counts else (logits, cache)
