"""``paged_flash_decode`` kernel time in the trace against the least time
its required work needs, per decode step and layer: the live context's K
and V read once at the cache's storage width, 4*H*hd FLOPs per context
token, the query read and the output written in f32."""
from bench import shapes

KERNEL = r"paged_flash_decode$"


def work(ctx_tokens, rows, config):
    H, KV, hd = config["n_heads"], config["n_kv_heads"], config["head_dim"]
    L = config["n_layers"]
    word = shapes.cache_word_bytes(config)
    flops = 4.0 * H * hd * ctx_tokens * L
    nbytes = (2.0 * KV * hd * word * ctx_tokens + 8.0 * rows * H * hd) * L
    return flops, nbytes


def read(v):
    if v.trace is None or v.peak is None:
        return None
    t = v.trace.kernel_s(KERNEL)
    if t <= 0:
        return None
    need = sum(shapes.roofline_s(*work(s.ctx_tokens, s.rows, v.config), v.peak)
               for s in v.steps)
    return 100.0 * need / t
