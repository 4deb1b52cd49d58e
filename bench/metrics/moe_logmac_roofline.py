"""``logmac`` kernel time in the trace against the least time its calls
need in an expert model, where it runs the attention projections and the
head: per contraction of M rows, 2*M*K*N FLOPs and the weight and
activations read once at the policy's posit width, f32 out."""
from bench import moe_shapes, shapes

KERNEL = r"logmac$"


def work(M, K, N, word):
    return 2.0 * M * K * N, (M * K + K * N) * word + 4.0 * M * N


def read(v):
    if v.trace is None or v.peak is None:
        return None
    t = v.trace.kernel_s(KERNEL)
    if t <= 0:
        return None
    word = shapes.posit_bytes(v.config)
    need = sum(shapes.roofline_s(*work(M, K, N, word), v.peak)
               for M, K, N in moe_shapes.attention_head_contractions(v))
    return 100.0 * need / t
