"""Posit codec kernel time in the trace against the least time its
required work needs: the activations of each weight contraction read in
f32 and written as posit words.  Weights need no per-step encode."""
from bench import shapes

KERNEL = r"posit_(en|de)code$"


def work(M, K, word):
    return 0.0, M * K * (4.0 + word)


def read(v):
    if v.trace is None or v.peak is None:
        return None
    t = v.trace.kernel_s(KERNEL)
    if t <= 0:
        return None
    word = shapes.posit_bytes(v.config)
    need = sum(shapes.roofline_s(*work(M, K, word), v.peak)
               for M, K, _ in shapes.contractions(v))
    return 100.0 * need / t
