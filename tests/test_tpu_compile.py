"""The main-path Pallas kernels compile for a TPU v5e at yi-34b shapes.

Interpret mode (every other test) checks what a kernel computes, not
whether Mosaic accepts it: block tiling, vector layouts and VMEM limits
are only checked by the chip's compiler.  Each test here compiles one
kernel for a *described* v5e (no chip attached) and asserts the
program holds the Mosaic kernel (``tpu_custom_call``).  Nothing runs.

The topology is described inside a fixture, never at import: only one
process may load the TPU compiler library at a time, so every worker
must collect these tests and only the one that runs them loads it.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import faultmap as fm
from repro.kernels.bitflip.bitflip import arena_bitflip_pallas
from repro.kernels.ecc.ecc import arena_ecc_pallas
from repro.kernels.flash_attention import faulty

# yi-34b decode shapes: 56 query heads over 8 KV heads of 128; one KV
# page is 8 slots (16 KiB, one arena block); 4 serving slots x 512-slot
# rings.
S, H, KH, D, PS, RING = 4, 56, 8, 128, 8, 512
N_LP = RING // PS
N_PAGES = S * N_LP + 1
WPRL2 = 9
U32, I32, BF16 = jnp.uint32, jnp.int32, jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described chip's executables are written to a persistent cache
    # but cannot be read back without the chip: keep it off.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("method,ecc,telemetry", [
    ("word", False, False), ("bitwise", False, False),
    ("word", True, True)])
def test_paged_decode_attention_compiles(one_chip, method, ecc, telemetry):
    def fn(q, k, v, pos, ptab, qpos, kb, kt, vb, vt):
        return faulty.paged_decode_attention(
            q, k, v, pos, ptab, q_pos=qpos, k_tables=(kb, kt),
            v_tables=(vb, vt), seed=7, method=method,
            words_per_row_log2=WPRL2, ecc=ecc, inject=True,
            telemetry=telemetry, interpret=False)
    tab = [((N_PAGES,), U32), ((N_PAGES, fm.NUM_THR_COLS), U32)]
    _compile(fn, one_chip, ((S, 1, H, D), BF16), ((N_PAGES, PS, KH, D), BF16),
             ((N_PAGES, PS, KH, D), BF16), ((N_PAGES, PS), I32),
             ((S, N_LP), I32), ((S,), I32), *tab, *tab)


@pytest.mark.parametrize("s,h,n_lp,ecc,inject,telemetry", [
    pytest.param(3, 16, 256, True, True, True, id="internlm2-ecc"),
    pytest.param(3, 16, 256, False, False, False, id="internlm2-guardband"),
    pytest.param(6, 56, 160, True, True, False, id="yi-ecc")])
def test_paged_decode_attention_compiles_at_cell_shapes(
        one_chip, s, h, n_lp, ecc, inject, telemetry):
    """The benchmark cells' shapes (8 KV heads of 128, a 2048- or
    1280-slot ring, one page per slot lane plus scratch): the kernel's
    two 16-page VMEM blocks per operand and their DMAs fit a v5e."""
    n_pages = s * n_lp + 1

    def fn(q, k, v, pos, ptab, qpos, kb, kt, vb, vt):
        return faulty.paged_decode_attention(
            q, k, v, pos, ptab, q_pos=qpos, k_tables=(kb, kt),
            v_tables=(vb, vt), seed=7, method="word",
            words_per_row_log2=WPRL2, ecc=ecc, inject=inject,
            telemetry=telemetry, interpret=False)
    tab = [((n_pages,), U32), ((n_pages, fm.NUM_THR_COLS), U32)]
    _compile(fn, one_chip, ((s, 1, h, D), BF16),
             ((n_pages, PS, KH, D), BF16), ((n_pages, PS, KH, D), BF16),
             ((n_pages, PS), I32), ((s, n_lp), I32), ((s,), I32), *tab,
             *tab)


def test_faulty_decode_attention_compiles(one_chip):
    """Page-granular tables, as ``generate()`` replays a request on its
    scheduler pages: one 8-slot page is one 4096-word arena block, and a
    tile is the paged kernel's block of pages."""
    b, words_log2 = 2, 12
    bkv = faulty.block_pages(N_LP, PS, KH * D // 2) * PS
    nb = b * RING * KH * D // 2 >> words_log2

    def fn(q, k, v, pos, kb, kt, vb, vt, qpos):
        return faulty.faulty_decode_attention(
            q, k, v, pos, q_pos=qpos, k_tables=(kb, kt), v_tables=(vb, vt),
            k_word0=jnp.uint32(0), v_word0=jnp.uint32(0), seed=7,
            method="word", words_per_row_log2=WPRL2, ecc=False, inject=True,
            clean_slot=jnp.int32(3), bkv=bkv, words_log2=words_log2,
            interpret=False)
    tab = [((nb,), U32), ((nb, fm.NUM_THR_COLS), U32)]
    _compile(fn, one_chip, ((b, 1, H, D), BF16), ((b, RING, KH, D), BF16),
             ((b, RING, KH, D), BF16), ((b, RING), I32), *tab, *tab,
             ((), I32))


@pytest.mark.parametrize("ecc", [False, True])
def test_arena_kernels_compile(one_chip, ecc):
    nb = 64

    def fn(arena, base, thr):
        if ecc:
            return arena_ecc_pallas(arena, base, thr, seed=7,
                                    words_per_row_log2=WPRL2,
                                    interpret=False)
        return arena_bitflip_pallas(arena, base, thr, seed=7,
                                    method="bitwise",
                                    words_per_row_log2=WPRL2,
                                    interpret=False)
    _compile(fn, one_chip, ((nb * 8, 512), U32), ((nb,), U32),
             ((nb, fm.NUM_THR_COLS), U32))
