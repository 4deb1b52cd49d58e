"""Plain float32 reference of the sparse-expert decoder the configurations
describe, for a device that holds a share of each layer's experts.

Pre-norm blocks: RMSNorm -> GQA attention -> residual; RMSNorm -> experts
-> residual; final RMSNorm; output head tied to the embedding.

* Attention: layer ``l`` is full attention when ``l % local_global_period
  == local_global_period - 1`` (every layer without a period), else it
  sees the last ``window`` positions (itself included).  Rotary positions
  rotate the two halves of each head (theta ``rope_theta``); with
  ``yarn_factor`` the full layers use YaRN's frequencies (below) and
  multiply cos and sin by ``yarn_attention_factor``.
* Experts: the router (``d_model x router_experts``) scores every expert;
  each token takes the ``top_k`` largest softmax probabilities and
  renormalizes them to sum to 1.  The device holds experts
  ``[expert_offset, expert_offset + n_experts)``; a token's output is the
  sum, over its chosen experts that are held, of weight x
  ``silu(x Wg) * (x Wi) Wo``.  What the experts held elsewhere add is left
  out, as the device that computes this share leaves it out.

YaRN (Hugging Face ``transformers``' ``_compute_yarn_parameters``), with
dim = head_dim, base = rope_theta, s = yarn_factor, L =
yarn_original_max_pos:  pos_i = base ** (2i / dim);  corr(r) = dim *
ln(L / (2 pi r)) / (2 ln base);  low = max(floor(corr(beta_fast)), 0),
high = min(ceil(corr(beta_slow)), dim - 1);  ramp_i = clip((i - low) /
(high - low), 0, 1);  inv_freq_i = ramp_i / (s pos_i) + (1 - ramp_i) /
pos_i.

Everything is float32 with ``Precision.HIGHEST`` matmuls over the full
causal sequence, one layer at a time: no kernels, cache, paging or
batching of requests.  It imports nothing of the program; it reads the
weight tree the benchmark made, by its leaf names.

``widest_gaps`` is the output check: for each sequence (prompt followed by
the tokens the program served), the largest amount by which a served
token's logit lies below the reference's best logit at that position.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
# the configuration keys this reference reads and the MLPs it knows
READS = frozenset({"family", "n_layers", "d_model", "n_heads", "n_kv_heads",
                   "head_dim", "d_ff", "vocab", "mlp", "rope_theta",
                   "norm_eps", "tie_embeddings", "dtype", "window",
                   "local_global_period", "yarn_factor",
                   "yarn_original_max_pos", "yarn_beta_fast",
                   "yarn_beta_slow", "yarn_attention_factor", "n_experts",
                   "router_experts", "expert_offset", "top_k"})
# the published config's own keys, which a configuration states beside
# these and the program checks against its own; the harness refuses a
# configuration with keys outside KEYS or another MLP
PUBLISHED = frozenset({
    "attention_bias", "hidden_act", "hidden_size", "intermediate_size",
    "layer_types", "mlp_layer_types", "max_position_embeddings",
    "max_window_layers", "model_type", "moe_intermediate_size",
    "norm_topk_prob", "num_attention_heads", "num_experts",
    "num_experts_per_tok", "num_hidden_layers", "num_key_value_heads",
    "rms_norm_eps", "rope_parameters", "sliding_window",
    "tie_word_embeddings", "vocab_size", "use_sliding_window"})
KEYS = READS | PUBLISHED
MLPS = ("silu_gated",)
# tokens per block: bounds the [rows, heads, T, T] scores
BLOCK_TOKENS = 4096


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _yarn_inv_freq(hd, theta, factor, orig, beta_fast, beta_slow):
    def corr(rot):
        return hd * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), hd - 1)
    if low == high:
        high += 0.001
    pos = theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ramp = np.clip((np.arange(hd // 2) - low) / (high - low), 0, 1)
    return (ramp / (factor * pos) + (1 - ramp) / pos).astype(np.float32)


def _rope(x, inv_freq, scale):
    T, half = x.shape[1], x.shape[-1] // 2
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq   # [T, half]
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _dims(cfg: dict) -> tuple:
    """The static numbers ``_layer`` needs, from the configuration."""
    period = cfg.get("local_global_period") or 1
    return (cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"],
            float(cfg["rope_theta"]), float(cfg["norm_eps"]),
            int(cfg.get("window") or 0), int(period),
            float(cfg.get("yarn_factor", 0.0)),
            int(cfg.get("yarn_original_max_pos", 0)),
            float(cfg.get("yarn_beta_fast", 32.0)),
            float(cfg.get("yarn_beta_slow", 1.0)),
            float(cfg.get("yarn_attention_factor", 1.0)),
            cfg["top_k"], int(cfg.get("expert_offset", 0)))


@functools.partial(jax.jit, static_argnames=("dims",))
def _layer(x, layers, l, dims):
    (H, KV, hd, theta, eps, window, period, yf, yorig, ybf, ybs, yatt, k,
     e0) = dims
    p = jax.tree.map(lambda a: a[l].astype(jnp.float32), layers)
    N, T, d = x.shape
    full = (l % period) == period - 1
    plain = (theta ** -(np.arange(0, hd, 2) / hd)).astype(np.float32)
    inv_freq, scale = jnp.asarray(plain), jnp.float32(1.0)
    if yf:
        yarn = _yarn_inv_freq(hd, theta, yf, yorig, ybf, ybs)
        inv_freq = jnp.where(full, jnp.asarray(yarn), inv_freq)
        scale = jnp.where(full, yatt, 1.0)

    h = _rms(x, p["ln1"]["g"], eps)
    q = jnp.matmul(h, p["attn"]["wq"]["w"], precision=HI).reshape(N, T, H, hd)
    kk = jnp.matmul(h, p["attn"]["wk"]["w"], precision=HI).reshape(N, T, KV, hd)
    v = jnp.matmul(h, p["attn"]["wv"]["w"], precision=HI).reshape(N, T, KV, hd)
    q, kk = _rope(q, inv_freq, scale), _rope(kk, inv_freq, scale)
    kk = jnp.repeat(kk, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("nthd,nshd->nhts", q, kk, precision=HI) * hd ** -0.5
    t_pos, s_pos = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    mask = s_pos <= t_pos
    if window:
        mask &= full | (s_pos > t_pos - window)
    s = jnp.where(mask, s, -jnp.inf)
    o = jnp.einsum("nhts,nshd->nthd", jax.nn.softmax(s, -1), v,
                   precision=HI).reshape(N, T, H * hd)
    x = x + jnp.matmul(o, p["attn"]["wo"]["w"], precision=HI)

    h = _rms(x, p["ln2"]["g"], eps).reshape(N * T, d)
    return x + experts(h, p["moe"], k, e0).reshape(N, T, d)


def experts(h, m, k: int, e0: int):
    """The held experts' part of the expert layer's output for tokens
    ``h`` [n, d] (f32 weights ``m``: the layer's ``moe`` subtree)."""
    probs = jax.nn.softmax(jnp.matmul(h, m["router"]["w"], precision=HI), -1)
    gates, ids = jax.lax.top_k(probs, k)
    gates = gates / gates.sum(-1, keepdims=True)

    def expert(y, e):
        wi, wg, wo = (m[n]["w"][e] for n in ("wi", "wg", "wo"))
        weight = jnp.sum(jnp.where(ids == e0 + e, gates, 0.0), -1)
        u = jax.nn.silu(jnp.matmul(h, wg, precision=HI)) * jnp.matmul(
            h, wi, precision=HI)
        return y + weight[:, None] * jnp.matmul(u, wo, precision=HI), None

    held = m["wi"]["w"].shape[0]
    y, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(held))
    return y


def _head(x, g, emb, vocab, eps):
    h = _rms(x, g.astype(jnp.float32), eps)
    return jnp.einsum("ntd,vd->ntv", h, emb[:vocab].astype(jnp.float32),
                      precision=HI)


@functools.partial(jax.jit, static_argnames=("vocab", "eps"))
def _head_gap(x, g, emb, targets, mask, vocab, eps):
    logits = _head(x, g, emb, vocab, eps)
    best = logits.max(-1)
    got = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.where(mask, best - got, 0.0).max(-1)


def _hidden(params, cfg: dict, tokens):
    x = params["embed"]["e"][jnp.asarray(tokens)].astype(jnp.float32)
    dims = _dims(cfg)
    for l in range(cfg["n_layers"]):
        x = _layer(x, params["layers"], l, dims)
    return x


def logits(params, cfg: dict, tokens) -> jax.Array:
    """Reference logits [N, T, vocab] of token ids [N, T]."""
    return jax.jit(_head, static_argnames=("vocab", "eps"))(
        _hidden(params, cfg, tokens), params["ln_f"]["g"],
        params["embed"]["e"], vocab=cfg["vocab"], eps=float(cfg["norm_eps"]))


def widest_gaps(params, cfg: dict, seqs) -> list[float]:
    """``seqs``: list of ``(tokens, n_prompt)``; ``tokens`` is the prompt
    followed by the served tokens.  Returns each sequence's widest gap."""
    order = sorted(range(len(seqs)), key=lambda i: -len(seqs[i][0]))
    gaps = [0.0] * len(seqs)
    while order:
        # longest first: the block's first sequence sets its padded length
        T = -(-(len(seqs[order[0]][0]) - 1) // 128) * 128
        block = order[:max(1, BLOCK_TOKENS // T)]
        order = order[len(block):]
        inp = np.zeros((len(block), T), np.int32)
        tgt = np.zeros((len(block), T), np.int32)
        msk = np.zeros((len(block), T), bool)
        for r, i in enumerate(block):
            toks, n_prompt = seqs[i]
            n = len(toks) - 1
            inp[r, :n] = toks[:-1]
            tgt[r, :n] = toks[1:]
            msk[r, n_prompt - 1:n] = True
        out = _head_gap(_hidden(params, cfg, inp), params["ln_f"]["g"],
                        params["embed"]["e"], jnp.asarray(tgt),
                        jnp.asarray(msk), vocab=cfg["vocab"],
                        eps=float(cfg["norm_eps"]))
        for r, i in enumerate(block):
            gaps[i] = float(out[r])
    return gaps
