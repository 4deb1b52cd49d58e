"""Pallas kernel validation (interpret=True on CPU): shape/dtype sweeps
against the pure-jnp oracles in kernels/ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import posit as P
from repro.core.engine import from_variant, EulerConfig
from repro.kernels import ops, ref
from repro.kernels.logmac import decode_planes_raw

CFGS = [P.POSIT8, P.BPOSIT8, P.POSIT16, P.BPOSIT16, P.POSIT32, P.BPOSIT32]


def _rand(rng, shape, scale_pow=6):
    x = rng.normal(size=shape).astype(np.float32)
    return x * np.exp2(rng.integers(-scale_pow, scale_pow, size=shape)).astype(np.float32)


@pytest.mark.parametrize("pc", CFGS, ids=lambda c: c.name)
@pytest.mark.parametrize("shape", [(37,), (64, 33), (5, 7, 11)])
def test_encode_kernel_matches_ref(pc, shape, rng):
    x = jnp.asarray(_rand(rng, shape))
    got = ops.encode(x, pc, block=128)
    want = ref.ref_encode(x, pc)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("pc", CFGS, ids=lambda c: c.name)
def test_decode_kernel_matches_ref(pc, rng):
    pats = jnp.asarray(
        rng.integers(0, 1 << min(pc.n_bits, 16), size=300), jnp.uint32)
    got = ops.decode(pats, pc, block=128)
    want = ref.ref_decode(pats, pc)
    got, want = np.asarray(got), np.asarray(want)
    # exclude NaR and f32-subnormal magnitudes: this host runs with FTZ
    # enabled (preloaded fast-math lib), which flushes the kernel's
    # two-factor 2^e product for |x| < 2^-126 in interpret mode
    mask = ~np.isnan(want) & (np.abs(want) > 2.0 ** -120)
    np.testing.assert_allclose(got[mask], want[mask], rtol=1e-6)


@pytest.mark.parametrize("width,variant", [(8, "L-1"), (8, "L-21b"),
                                           (16, "L-2"), (16, "L-21b"),
                                           (32, "L-22b")])
def test_inkernel_planes_match_core(width, variant, rng):
    """decode_planes_raw (the kernel body) == core ilm plane construction."""
    cfg = from_variant(width, variant)
    pc = cfg.posit
    pats = jnp.asarray(rng.integers(0, 1 << min(pc.n_bits, 16), size=512),
                       jnp.uint32)
    got_v, got_r = decode_planes_raw(pats, pc, cfg.stages, cfg.trunc,
                                     cfg.sublane)
    want_v, want_r = ref.ref_planes(pats, cfg)
    np.testing.assert_allclose(np.asarray(got_v), np.asarray(want_v), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got_r), np.asarray(want_r), rtol=1e-6)


@pytest.mark.parametrize("mnk", [(32, 16, 48), (128, 128, 128), (65, 33, 70),
                                 (256, 64, 200)])
@pytest.mark.parametrize("variant", ["L-21b", "L-2"])
def test_logmac_kernel_matches_ref(mnk, variant, rng):
    M, N, K = mnk
    cfg = from_variant(16, variant)
    pc = cfg.posit
    a = ref.ref_encode(jnp.asarray(_rand(rng, (M, K), 3)), pc)
    b = ref.ref_encode(jnp.asarray(_rand(rng, (K, N), 3)), pc)
    got = ops.logmac_matmul(a, b, cfg, bm=32, bn=32, bk=32)
    want = ref.ref_logmac(a, b, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("width", [8, 16, 32])
def test_fused_path_close_to_engine(width, rng):
    """Posit-encode + logmac kernel ~= euler_matmul on floats (same math,
    different plumbing — fused path encodes once, engine path quantizes)."""
    from repro.core.engine import euler_matmul
    cfg = from_variant(width, "L-21b", pre_scale=False)
    x = jnp.asarray(rng.normal(size=(48, 64)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(64, 24)).astype(np.float32))
    fused = ops.euler_matmul_fused(x, w, cfg, bm=16, bn=8, bk=32)
    engine = euler_matmul(x, w, cfg)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(engine),
                               rtol=1e-4, atol=1e-3)


def test_logmac_zero_padding_is_neutral(rng):
    """Padding with posit-zero patterns must not change the product."""
    cfg = from_variant(16, "L-21b")
    pc = cfg.posit
    a = ref.ref_encode(jnp.asarray(rng.normal(size=(17, 19)), jnp.float32), pc)
    b = ref.ref_encode(jnp.asarray(rng.normal(size=(19, 13)), jnp.float32), pc)
    got = ops.logmac_matmul(a, b, cfg, bm=16, bn=16, bk=16)  # forces padding
    want = ref.ref_logmac(a, b, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


# (E, M, K, N, bk, bn): K and N multiples of 128 whose only block divisors
# are awkward (3, 5, 7 x 128); E > 0 runs logmac_experts over E experts
BLOCKED = [(0, 16, 384, 640, 384, 640), (0, 64, 640, 384, 640, 384),
           (0, 96, 896, 384, 896, 128), (0, 200, 384, 896, 128, 896),
           (0, 64, 384, 640, 128, 640), (3, 16, 384, 640, 384, 640)]


@pytest.mark.parametrize("case", BLOCKED, ids=lambda c: "-".join(map(str, c)))
def test_blocked_logmac_bit_equal_to_128_tiles(case, rng):
    """A grid step that walks a (bk, bn) weight block in 128x128 sub-tiles
    adds each output's K sub-tiles in the order 128^3 tiles would: the
    same f32 words."""
    E, M, K, N, bk, bn = case
    cfg = from_variant(16, "L-21b")
    lead = (E,) if E else ()
    a = ref.ref_encode(jnp.asarray(_rand(rng, lead + (M, K), 3)), cfg.posit)
    b = ref.ref_encode(jnp.asarray(_rand(rng, lead + (K, N), 3)),
                       cfg.posit).astype(jnp.uint16)
    fn = ops.logmac_experts_matmul if E else ops.logmac_matmul
    bm = min(128, M)
    want = fn(a, b, cfg, bm=bm, bn=128, bk=128)
    got = fn(a, b, cfg, bm=bm, bn=bn, bk=bk)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# (M, K, N): Yi-6B decode (batch 64) and a 256-token prefill; Mellum2's
# expert stack (128 rows a held expert), attention and 98,304-id head
CELL_SHAPES = [(64, 4096, 11008), (64, 11008, 4096), (64, 4096, 4096),
               (64, 4096, 512), (64, 4096, 64000), (256, 4096, 11008),
               (128, 2304, 896), (128, 896, 2304), (128, 2304, 4096),
               (128, 4096, 2304), (128, 2304, 98304)]


@pytest.mark.parametrize("mkn", CELL_SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_logmac_blocks_divide_and_fit(mkn):
    """The chosen blocks divide the extents (a held weight is not padded),
    fit the VMEM budget, and span many 128x128 sub-tiles per grid step."""
    from repro.kernels.logmac import grid_counts, vmem_bytes
    from repro.numerics.backends import LOGMAC_VMEM, _blocks
    M, K, N = mkn
    bm, bn, bk = _blocks(M, K, N, 4, 2)
    assert bm == min(128, M)
    assert K % bk == 0 and N % bn == 0
    assert vmem_bytes(bm, bn, bk, 4, 2) <= LOGMAC_VMEM
    steps, tiles = grid_counts((), M, K, N, bm, bn, bk)
    assert tiles == -(-M // bm) * (K // 128) * (N // 128)
    assert tiles >= 16 * steps


@pytest.mark.parametrize("extent,blocks", [(40, [40]), (128, [128]),
                                           (200, [256, 128]),
                                           (896, [896, 128]),
                                           (768, [768, 384, 256, 128])])
def test_logmac_block_choices(extent, blocks):
    """Blocks are multiples of 128 dividing the 128-padded extent, largest
    first; below 128 one small tile, as before."""
    from repro.numerics.backends import _block_choices
    assert _block_choices(extent) == blocks


def test_logmac_blocks_keep_given_extents():
    from repro.numerics.backends import _blocks
    assert _blocks(64, 4096, 11008, 4, 2, bn=128, bk=128) == (64, 128, 128)
    bm, bn, bk = _blocks(64, 4096, 11008, 4, 2, bm=32, bk=256)
    assert (bm, bk) == (32, 256) and 11008 % bn == 0 and bn > 128


def test_held_weight_not_padded_per_call():
    """Blocks over a held weight of awkward extents (3 and 5 x 128) pad
    only the activation's rows, never the weight's words."""
    from repro.numerics import stored
    from repro.numerics.backends import PallasBackend
    cfg = from_variant(16, "L-21b")
    w = jnp.ones((384, 640), jnp.float32)
    held = stored.hold(w, cfg.posit)
    hlo = jax.jit(lambda a, h: PallasBackend().matmul(a, h, cfg)).lower(
        jnp.ones((5, 384), jnp.float32), held).as_text()
    pads = [line for line in hlo.splitlines() if "stablehlo.pad" in line]
    assert pads and not any("xui16>" in line for line in pads), pads
