"""Trip-count-aware cost model over the traced jaxpr.

XLA's ``compiled.cost_analysis()`` counts a while-loop body ONCE, so a
46-layer ``lax.scan`` under-reports FLOPs by ~46x.  The jaxpr still has the
structure: ``scan`` equations carry a static ``length``, so walking the
closed jaxpr and multiplying nested bodies by their trip counts yields exact
FLOP/traffic totals for the *global* (unpartitioned) program.

Counted:
  * dot FLOPs: 2 * batch * M * N * K per dot_general (plus conv as dots)
  * elementwise/other FLOPs: 1 per output element of arithmetic primitives
  * dot traffic: operand + output bytes per dot (fusion-free upper bound on
    HBM traffic of the matmul pipeline)
  * shard_map bodies are multiplied by the mesh size (the body text is
    per-device)
"""
from __future__ import annotations

import numpy as np
from jax.extend import core as jcore

_ARITH = {
    "add", "sub", "mul", "div", "max", "min", "exp", "log", "tanh", "rsqrt",
    "sqrt", "neg", "abs", "floor", "round", "sign", "logistic", "pow",
    "integer_pow", "erf", "cumsum", "reduce_sum", "reduce_max", "select_n",
    "and", "or", "xor", "shift_left", "shift_right_logical",
    "shift_right_arithmetic", "lt", "le", "gt", "ge", "eq", "ne",
}

_SUBJAXPR_PARAMS = ("jaxpr", "call_jaxpr", "cond_jaxpr", "body_jaxpr")


def _avals(vs):
    return [v.aval for v in vs]


def _nbytes(aval) -> int:
    return int(np.prod(aval.shape)) * aval.dtype.itemsize if aval.shape else \
        aval.dtype.itemsize


def _dot_flops(eqn) -> tuple[int, int]:
    lhs, rhs = _avals(eqn.invars)[:2]
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    csize = int(np.prod([lhs.shape[i] for i in lc])) if lc else 1
    bsize = int(np.prod([lhs.shape[i] for i in lb])) if lb else 1
    m = int(np.prod([lhs.shape[i] for i in range(lhs.ndim)
                     if i not in lc and i not in lb])) or 1
    n = int(np.prod([rhs.shape[i] for i in range(rhs.ndim)
                     if i not in rc and i not in rb])) or 1
    flops = 2 * bsize * m * n * csize
    traffic = _nbytes(lhs) + _nbytes(rhs) + 4 * bsize * m * n  # f32 out
    return flops, traffic


def _sub_jaxprs(eqn):
    out = []
    for k, v in eqn.params.items():
        vals = v if isinstance(v, (list, tuple)) else [v]
        for item in vals:
            if isinstance(item, jcore.ClosedJaxpr):
                out.append(item.jaxpr)
            elif isinstance(item, jcore.Jaxpr):
                out.append(item)
    return out


def analyze_jaxpr(jaxpr, mult: float = 1.0, acc=None):
    """Recursive walk.  Returns dict with dot_flops, ew_flops, dot_traffic."""
    if acc is None:
        acc = {"dot_flops": 0.0, "ew_flops": 0.0, "dot_traffic": 0.0,
               "dots": 0}
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        m = mult
        if name == "scan":
            m = mult * eqn.params.get("length", 1)
        elif name == "shard_map":
            mesh = eqn.params.get("mesh")
            if mesh is not None:
                m = mult * int(np.prod(list(mesh.shape.values())))
        elif name == "while":
            m = mult  # unknown trip count: counted once (we only use scan)
        if name == "dot_general":
            f, t = _dot_flops(eqn)
            acc["dot_flops"] += f * mult
            acc["dot_traffic"] += t * mult
            acc["dots"] += 1
        elif name in _ARITH and eqn.outvars:
            out = eqn.outvars[0].aval
            acc["ew_flops"] += (int(np.prod(out.shape)) if out.shape else 1) * mult
        for sub in _sub_jaxprs(eqn):
            analyze_jaxpr(sub, m, acc)
    return acc


def analyze(fn, *abstract_args):
    """Trace ``fn`` with abstract args and analyze the closed jaxpr."""
    import jax
    closed = jax.make_jaxpr(fn)(*abstract_args)
    return analyze_jaxpr(closed.jaxpr)
