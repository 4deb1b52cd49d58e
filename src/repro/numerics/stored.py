"""Contraction weights held as posit words, made once.

A serving weight is a constant, so its per-tensor power-of-2 scale and its
posit words are the same at every step.  ``hold`` computes them once, with
the same ops the ``pallas`` backend runs per call (the contraction-first
layout, ``core.engine._pow2_scale`` over each 2-D slice, the codec's
``encode_body``), and stores the words at the format's own width: uint16
for Posit-16, uint8 for Posit-8.

A :class:`PositWeight` carries the float leaf beside its words.  A backend
that reads words (``Backend.reads_words``) uses them when the resolved
config is at their format; every other backend, wrapper or ladder level is
handed the float operand (``PositWeight.operand``) and contracts it per
call, as it would have without the words.

``tally()`` counts, while a program traces, how many held weights each
contraction read as stored words and how many it encoded per call, and
on request the ``logmac`` grid steps and sub-tile products they ran.
"""
from __future__ import annotations

import contextlib
import functools
import threading

import jax
import jax.numpy as jnp

from repro.core import engine as _E
from repro.core import posit as _P

STORED, PER_CALL = "stored", "per_call"
# the ``logmac`` grid steps and 128x128 sub-tile products the contractions
# run (``logmac.grid_counts``)
LOGMAC_STEPS, LOGMAC_TILES = "logmac_steps", "logmac_tiles"


@jax.tree_util.register_pytree_node_class
class PositWeight:
    """A weight ``w`` with its posit words and scale.

    ``words`` ``[..., K, N]`` hold the trailing 2-D matrix of ``w`` in
    contraction-first layout (``axis`` of that matrix is the contracted
    one, K), encoded in format ``pc`` after division by ``scale``
    ``[...]``, one power of 2 per matrix.  ``dtype``: what the per-call
    path casts ``w`` to before contracting it (None: as held).  Leading
    dims are layer stacks; ``lax.scan`` slices all three leaves together.
    """

    __slots__ = ("w", "words", "scale", "pc", "axis", "dtype")

    def __init__(self, w, words, scale, pc: _P.PositConfig, axis: int,
                 dtype=None):
        self.w, self.words, self.scale = w, words, scale
        self.pc, self.axis, self.dtype = pc, axis, dtype

    def tree_flatten(self):
        return (self.w, self.words, self.scale), (self.pc, self.axis,
                                                  self.dtype)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    def operand(self):
        """The float operand the per-call path contracts."""
        return self.w if self.dtype is None else self.w.astype(self.dtype)


def contraction_first(m, axis: int):
    """A 2-D operand as the f32 ``[K, N]`` matrix the kernels contract,
    dim ``axis`` first: the ops ``PallasBackend`` applies to a weight per
    call (transpose, reshape, cast), so both scale and encode the same
    values."""
    perm = (axis,) + tuple(d for d in range(m.ndim) if d != axis)
    t = jnp.transpose(m, perm)
    return t.reshape(t.shape[0], -1).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("pc", "axis", "dtype"))
def _encode(x, pc, axis, dtype):
    from repro.kernels.posit_codec import encode_body  # deferred: kernels

    def one(m):
        if dtype is not None:
            m = m.astype(dtype)
        f = contraction_first(m, axis)
        s = _E._pow2_scale(f)
        return encode_body(f / s, pc).astype(pc.storage_dtype), s

    if x.ndim == 2:
        return one(x)
    lead = x.shape[:-2]
    # one slice at a time: the transient is one matrix, not the stack
    words, scale = jax.lax.map(one, x.reshape((-1,) + x.shape[-2:]))
    return words.reshape(lead + words.shape[1:]), scale.reshape(lead)


def hold(x, pc: _P.PositConfig, axis: int = 0, dtype=None) -> PositWeight:
    """Hold ``x`` (``[..., A, B]``) as posit words of format ``pc``,
    contracted over ``axis`` of its trailing matrix, after a cast to
    ``dtype`` when given."""
    dtype = None if dtype is None else jnp.dtype(dtype)
    words, scale = _encode(x, pc=pc, axis=axis, dtype=dtype)
    return PositWeight(x, words, scale, pc, axis, dtype)


def held(tree) -> list[PositWeight]:
    """Every held weight in ``tree``."""
    return [x for x in jax.tree.leaves(
        tree, is_leaf=lambda x: isinstance(x, PositWeight))
        if isinstance(x, PositWeight)]


_TLS = threading.local()


@contextlib.contextmanager
def tally(kinds=(STORED, PER_CALL)):
    """Count held-weight contractions traced inside: ``{"stored": n,
    "per_call": m}``, or whichever ``kinds`` are asked for.  Counted at
    trace time, so a scanned layer's contraction counts once."""
    counts = dict.fromkeys(kinds, 0)
    stack = _TLS.__dict__.setdefault("stack", [])
    stack.append(counts)
    try:
        yield counts
    finally:
        stack.pop()


def count(kind: str, n: int = 1) -> None:
    for counts in getattr(_TLS, "stack", ()):
        if kind in counts:
            counts[kind] += n
