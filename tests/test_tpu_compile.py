"""The main path's kernels compile for a TPU v5e at gemma2-2b widths.

Nothing runs: each test lowers and compiles for one chip of a described
``v5e:2x2`` topology (the TPU compiler is installed even where no chip is
attached) and checks that the program holds the Mosaic kernel
(``tpu_custom_call``).  This catches what interpret mode cannot: block
shapes off the (8, 128) tiling, casts Mosaic cannot lower, VMEM overuse.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import posit as P
from repro.core.engine import from_variant
from repro.kernels import logmac as LM
from repro.kernels import paged_decode as PD
from repro.kernels import posit_codec as PC
from repro.numerics.backends import PallasBackend

# gemma2-2b FULL widths, decode batch 4, 512-token slots of 16-token pages
D_MODEL, D_FF, VOCAB, N_HEADS, N_KV, HEAD_DIM = 2304, 9216, 256000, 8, 4, 288
BATCH, PAGE, N_LOGICAL = 4, 16, 32
NUM_PAGES = PD.RESERVED_PAGES + BATCH * N_LOGICAL


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without the chip: keep the cache out
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", enabled)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_hlo(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("width", [8, 16, 32])
def test_codec_compiles(one_chip, width):
    pc = from_variant(width, "L-21b").posit
    w = jax.ShapeDtypeStruct((D_MODEL, D_FF), jnp.float32, sharding=one_chip)
    x = jax.ShapeDtypeStruct((BATCH, D_MODEL), jnp.float32, sharding=one_chip)
    pat = jax.ShapeDtypeStruct((D_MODEL, D_FF), jnp.uint32, sharding=one_chip)
    for arg in (w, x):
        hlo = _compiled_hlo(
            lambda a: PC.posit_encode(a, pc, interpret=False), arg)
        assert "tpu_custom_call" in hlo
    hlo = _compiled_hlo(lambda a: PC.posit_decode(a, pc, interpret=False), pat)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("width,n", [(8, D_FF), (16, D_FF), (32, D_FF),
                                     (16, VOCAB)])
def test_logmac_compiles(one_chip, width, n):
    """Decode tiles: bm 8 at batch 4, bn/bk 128; n=VOCAB is the LM head."""
    cfg = from_variant(width, "L-21b")
    a = jax.ShapeDtypeStruct((8, D_MODEL), jnp.uint32, sharding=one_chip)
    b = jax.ShapeDtypeStruct((D_MODEL, n), jnp.uint32, sharding=one_chip)
    hlo = _compiled_hlo(
        lambda x, y: LM.logmac(x, y, cfg, bm=8, bn=128, bk=128,
                               interpret=False), a, b)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("dtype", [jnp.uint8, jnp.uint16, jnp.uint32],
                         ids=lambda d: jnp.dtype(d).name)
def test_paged_flash_decode_compiles(one_chip, dtype):
    cfg = from_variant(16, "L-21b")
    pc = P.storage_pc(dtype, cfg.posit)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pages = sds((NUM_PAGES, PAGE, N_KV, HEAD_DIM), dtype)
    hlo = _compiled_hlo(
        lambda q, k, v, t, pos, win: PD.paged_flash_decode(
            q, k, v, t, pos, win, pc=pc, cfg_qk=cfg, cfg_pv=cfg,
            softcap=50.0, interpret=False),
        sds((BATCH, 1, N_HEADS, HEAD_DIM), jnp.bfloat16), pages, pages,
        sds((BATCH, N_LOGICAL), jnp.int32), sds((BATCH,), jnp.int32),
        sds((), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_pallas_backend_projection_compiles(one_chip):
    """The backend's wrapping (pre-scale, tiling, padding) around the codec
    and logmac kernels, on a bf16 gemma2-2b MLP weight at decode batch."""
    cfg = from_variant(16, "L-21b")
    backend = PallasBackend(interpret=False)
    x = jax.ShapeDtypeStruct((BATCH, D_MODEL), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((D_MODEL, D_FF), jnp.bfloat16, sharding=one_chip)
    hlo = _compiled_hlo(lambda a, b: backend.matmul(a, b, cfg), x, w)
    assert hlo.count("tpu_custom_call") >= 3  # encode x, encode w, logmac


# Yi-6B widths at decode batch 64: an MLP weight and the 64,000-id head
YI_D, YI_FF, YI_VOCAB, YI_BATCH = 4096, 11008, 64000, 64


@pytest.mark.parametrize("n", [YI_FF, YI_VOCAB])
def test_logmac_reads_uint16_weight_words(one_chip, n):
    """Stored Posit-16 weight words enter ``logmac`` at 2 bytes: the kernel
    widens them, so the program holds no uint32 copy of the weight."""
    cfg = from_variant(16, "L-21b")
    a = jax.ShapeDtypeStruct((YI_BATCH, YI_D), jnp.uint32, sharding=one_chip)
    b = jax.ShapeDtypeStruct((YI_D, n), jnp.uint16, sharding=one_chip)
    hlo = _compiled_hlo(
        lambda x, y: LM.logmac(x, y, cfg, bm=YI_BATCH, bn=128, bk=128,
                               interpret=False), a, b)
    assert "tpu_custom_call" in hlo
    assert f"u16[{YI_D},{n}]" in hlo
    assert f"u32[{YI_D},{n}]" not in hlo


@pytest.mark.parametrize("n", [YI_FF, YI_VOCAB])
def test_pallas_backend_reads_held_words(one_chip, n):
    """A held weight costs the backend one activation encode and
    ``logmac``: no f32 or uint32 copy of the weight, no weight encode."""
    from repro.numerics import stored
    cfg = from_variant(16, "L-21b")
    backend = PallasBackend(interpret=False)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,  # noqa: E731
                                                 sharding=one_chip)
    x = sds((YI_BATCH, YI_D), jnp.bfloat16)

    def fn(a, w, words, scale):
        held = stored.PositWeight(w, words, scale, cfg.posit, 0)
        return backend.matmul(a, held, cfg)

    hlo = _compiled_hlo(fn, x, sds((YI_D, n), jnp.bfloat16),
                        sds((YI_D, n), jnp.uint16), sds((), jnp.float32))
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2
    for dt in ("u32", "f32"):
        assert f"{dt}[{YI_D},{n}]" not in hlo


# Mellum2-12B-A2.5B's expert layer on one chip: 16 held experts at d_model
# 2304 and expert width 896, at decode batch 128
MEL_E, MEL_D, MEL_F, MEL_ROWS = 16, 2304, 896, 128


@pytest.mark.parametrize("k,n", [(MEL_D, MEL_F), (MEL_F, MEL_D)])
def test_pallas_backend_reads_held_expert_words(one_chip, k, n):
    """A held expert stack costs the backend one activation encode and one
    ``logmac_experts`` call, which reads the uint16 words as they are: no
    uint32 or f32 copy of the expert words."""
    from repro.numerics import stored
    cfg = from_variant(16, "L-21b")
    backend = PallasBackend(interpret=False)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,  # noqa: E731
                                                 sharding=one_chip)

    def fn(a, w, words, scale):
        held = stored.PositWeight(w, words, scale, cfg.posit, 0)
        return backend.dot_general(a, held, (((2,), (1,)), ((0,), (0,))),
                                   cfg)

    hlo = _compiled_hlo(fn, sds((MEL_E, MEL_ROWS, k), jnp.bfloat16),
                        sds((MEL_E, k, n), jnp.bfloat16),
                        sds((MEL_E, k, n), jnp.uint16),
                        sds((MEL_E,), jnp.float32))
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2
    assert any(re.match(rf"\s*(ROOT )?%{LM.EXPERTS_NAME}(\.\d+)? = ", c)
               for c in calls), calls
    assert f"u16[{MEL_E},{k},{n}]" in hlo
    for dt in ("u32", "f32"):
        assert f"{dt}[{MEL_E},{k},{n}]" not in hlo


# (experts, rows, K, N) under the blocks ``PallasBackend`` picks: Yi-6B's
# MLP weights both ways and its 64,000-id head at decode batch 64, and
# Mellum2's held expert stack at 128 rows an expert
BLOCKED_CASES = [(0, YI_BATCH, YI_D, YI_FF), (0, YI_BATCH, YI_FF, YI_D),
                 (0, YI_BATCH, YI_D, YI_VOCAB),
                 (MEL_E, MEL_ROWS, MEL_D, MEL_F)]


@pytest.mark.parametrize("case", BLOCKED_CASES,
                         ids=lambda c: "x".join(map(str, c)))
def test_logmac_compiles_at_chosen_blocks(one_chip, case):
    """The chooser's blocks (many 128x128 sub-tiles a grid step, walked by
    an in-kernel loop) fit the v5e's VMEM and slice on its tiling."""
    from repro.numerics.backends import _blocks
    E, M, K, N = case
    cfg = from_variant(16, "L-21b")
    bm, bn, bk = _blocks(M, K, N, 4, 2)
    assert bn * bk > 128 * 128
    lead = (E,) if E else ()
    fn = LM.logmac_experts if E else LM.logmac
    a = jax.ShapeDtypeStruct(lead + (M, K), jnp.uint32, sharding=one_chip)
    b = jax.ShapeDtypeStruct(lead + (K, N), jnp.uint16, sharding=one_chip)
    hlo = _compiled_hlo(
        lambda x, y: fn(x, y, cfg, bm=bm, bn=bn, bk=bk, interpret=False),
        a, b)
    assert "tpu_custom_call" in hlo


def _named_kernel_cases():
    """Each kernel's body without its ``jax.jit`` wrapper (``__wrapped__``),
    as a refactor that inlines the wrapper would call it."""
    cfg = from_variant(16, "L-21b")
    pc = cfg.posit
    cache_pc = P.storage_pc(jnp.uint16, pc)
    encode, decode = PC.posit_encode.__wrapped__, PC.posit_decode.__wrapped__
    logmac, paged = LM.logmac.__wrapped__, PD.paged_flash_decode.__wrapped__
    experts = LM.logmac_experts.__wrapped__
    pages = ((NUM_PAGES, PAGE, N_KV, HEAD_DIM), jnp.uint16)
    return {
        PC.ENCODE_NAME: (lambda x: encode(x, pc, interpret=False),
                         [((BATCH, D_MODEL), jnp.float32)]),
        PC.DECODE_NAME: (lambda p: decode(p, pc, interpret=False),
                         [((BATCH, D_MODEL), jnp.uint32)]),
        LM.NAME: (lambda a, b: logmac(a, b, cfg, bm=8, bn=128, bk=128,
                                      interpret=False),
                  [((BATCH, D_MODEL), jnp.uint32),
                   ((D_MODEL, 256), jnp.uint32)]),
        LM.EXPERTS_NAME: (lambda a, b: experts(a, b, cfg, bm=8, bn=128,
                                               bk=128, interpret=False),
                          [((2, BATCH, D_MODEL), jnp.uint32),
                           ((2, D_MODEL, 256), jnp.uint16)]),
        PD.NAME: (lambda q, kv, t, pos: paged(
                      q, kv, kv, t, pos, None, pc=cache_pc, cfg_qk=cfg,
                      cfg_pv=cfg, interpret=False),
                  [((BATCH, 1, N_HEADS, HEAD_DIM), jnp.bfloat16), pages,
                   ((BATCH, N_LOGICAL), jnp.int32), ((BATCH,), jnp.int32)]),
    }


@pytest.mark.parametrize("name", [PC.ENCODE_NAME, PC.DECODE_NAME, LM.NAME,
                                  LM.EXPERTS_NAME, PD.NAME])
def test_kernel_is_named_in_the_program(one_chip, name):
    """The Mosaic custom call carries its module's name constant, which a
    trace shows as the op's kind, whatever wraps the kernel: unnamed, the
    call would take the name of the enclosing function."""
    fn, shapes = _named_kernel_cases()[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    calls = [line for line in _compiled_hlo(fn, *args).splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls and all(
        re.match(rf"\s*(ROOT )?%{name}(\.\d+)? = ", line)
        for line in calls), calls
