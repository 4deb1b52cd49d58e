"""Plain float32 reference of the dense decoder the configurations describe.

Pre-norm blocks: RMSNorm -> GQA attention with rotary positions (rotate
half, theta from the config) -> residual; RMSNorm -> MLP (squared ReLU,
tanh-GELU, or SiLU-gated: silu(x Wg) * (x Wi)) -> residual; final RMSNorm;
output head tied to the embedding.
Everything is float32 with ``Precision.HIGHEST`` matmuls, over the full
causal sequence: no kernels, cache, paging or batching of requests.  It
imports nothing of the program; it reads the weight tree the benchmark
made, by its leaf names.

``widest_gaps`` is the output check: for each sequence (prompt followed by
the tokens the program served), the largest amount by which a served
token's logit lies below the reference's best logit at that position.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
# the configuration keys this reference reads and the MLPs it knows; the
# harness refuses a configuration with other keys or another MLP
KEYS = frozenset({"n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
                  "d_ff", "vocab", "mlp", "rope_theta", "norm_eps",
                  "tie_embeddings", "dtype"})
MLPS = ("relu2", "gelu", "silu_gated")
# tokens per block: bounds the [rows, heads, T, T] scores and the MLP slab
BLOCK_TOKENS = 4096


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    T, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-jnp.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs      # [T, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _act(h, mlp):
    if mlp == "relu2":
        return jnp.square(jax.nn.relu(h))
    if mlp == "gelu":
        return jax.nn.gelu(h, approximate=True)
    raise ValueError(f"reference has no MLP {mlp!r}")


@functools.partial(jax.jit, static_argnames=("dims",))
def _layer(x, layers, l, dims):
    H, KV, hd, theta, eps, mlp = dims
    p = jax.tree.map(lambda a: a[l].astype(jnp.float32), layers)
    N, T, d = x.shape
    h = _rms(x, p["ln1"]["g"], eps)
    q = jnp.matmul(h, p["attn"]["wq"]["w"], precision=HI).reshape(N, T, H, hd)
    k = jnp.matmul(h, p["attn"]["wk"]["w"], precision=HI).reshape(N, T, KV, hd)
    v = jnp.matmul(h, p["attn"]["wv"]["w"], precision=HI).reshape(N, T, KV, hd)
    q, k = _rope(q, theta), _rope(k, theta)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("nthd,nshd->nhts", q, k, precision=HI) * hd ** -0.5
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("nhts,nshd->nthd", jax.nn.softmax(s, -1), v,
                   precision=HI).reshape(N, T, H * hd)
    x = x + jnp.matmul(o, p["attn"]["wo"]["w"], precision=HI)
    h = _rms(x, p["ln2"]["g"], eps)
    m = jnp.matmul(h, p["mlp"]["wi"]["w"], precision=HI)
    if mlp == "silu_gated":
        m = jax.nn.silu(jnp.matmul(h, p["mlp"]["wg"]["w"], precision=HI)) * m
    else:
        m = _act(m, mlp)
    return x + jnp.matmul(m, p["mlp"]["wo"]["w"], precision=HI)


@functools.partial(jax.jit, static_argnames=("vocab", "eps"))
def _head_gap(x, g, emb, targets, mask, vocab, eps):
    h = _rms(x, g.astype(jnp.float32), eps)
    logits = jnp.einsum("ntd,vd->ntv", h, emb[:vocab].astype(jnp.float32),
                        precision=HI)
    best = logits.max(-1)
    got = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.where(mask, best - got, 0.0).max(-1)


def widest_gaps(params, cfg: dict, seqs) -> list[float]:
    """``seqs``: list of ``(tokens, n_prompt)``; ``tokens`` is the prompt
    followed by the served tokens.  Returns each sequence's widest gap."""
    dims = (cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"],
            float(cfg["rope_theta"]), float(cfg["norm_eps"]), cfg["mlp"])
    emb, layers = params["embed"]["e"], params["layers"]
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
        x = emb[jnp.asarray(inp)].astype(jnp.float32)
        for l in range(cfg["n_layers"]):
            x = _layer(x, layers, l, dims)
        out = _head_gap(x, params["ln_f"]["g"], emb, jnp.asarray(tgt),
                        jnp.asarray(msk), vocab=cfg["vocab"],
                        eps=float(cfg["norm_eps"]))
        for r, i in enumerate(block):
            gaps[i] = float(out[r])
    return gaps
