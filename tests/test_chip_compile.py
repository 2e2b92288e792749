"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Interpret mode runs a kernel body on the CPU, but it does not apply the
chip's rules: Mosaic's (8, 128) block tiling and the 16 MiB of scoped
VMEM a kernel may use. Here each kernel is compiled for one chip of a
described (not attached) ``v5e:2x2`` topology, from shapes alone, so a
kernel the chip would refuse fails here. Nothing runs, so these say
nothing about results or speed.

This is the only test file that describes the chip. The topology is
described inside a module fixture, never at import: only one process may
load the TPU library at a time, and every test worker imports every test
file. The fixture skips where no topology can be described.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import ops as kops
from repro.kernels import rmsnorm as rn
from repro.kernels.paged_ssm import max_write_pages

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32

# qwen3-1.7b serving: 16 query heads over 8 kv heads of 128, vocab 151936,
# 8 slots, 16-token pages, a 256-token prefill bucket
QWEN3 = dict(H=16, Hkv=8, hd=128, V=151936)
# falcon-mamba-7b: d_inner = 2 * 4096 rows over d_state 16
FALCON_MAMBA = dict(R=8192, ds=16)
SLOTS, PAGE, POOL_PAGES, TABLE = 8, 16, 256, 16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a described-chip compile would be written to a persistent cache
    # that no CPU run can read back
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(one_chip, fn, *shapes):
    """Compile ``fn`` for the described chip; return the compiled HLO."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("S", [1, 256])
def test_paged_attention_compiles_at_qwen3_widths(one_chip, S):
    c = QWEN3
    hlo = _compile(
        one_chip,
        lambda q, k, v, t, n: kops.paged_attention(q, k, v, t, n,
                                                   mode="pallas"),
        ((SLOTS, S, c["H"], c["hd"]), BF16),
        ((POOL_PAGES, PAGE, c["Hkv"], c["hd"]), BF16),
        ((POOL_PAGES, PAGE, c["Hkv"], c["hd"]), BF16),
        ((SLOTS, TABLE), I32), ((SLOTS,), I32))
    assert "tpu_custom_call" in hlo


def test_topk_topp_mask_compiles_at_qwen3_vocab(one_chip):
    hlo = _compile(
        one_chip,
        lambda x, k, p: kops.topk_topp_mask(x, k, p, mode="pallas"),
        ((SLOTS, QWEN3["V"]), F32), ((SLOTS,), I32), ((SLOTS,), F32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("S", [1, 64])
def test_paged_ssm_update_compiles_at_falcon_mamba_widths(one_chip, S):
    R, ds = FALCON_MAMBA["R"], FALCON_MAMBA["ds"]
    W = max_write_pages(S, PAGE)
    hlo = _compile(
        one_chip,
        lambda *a: kops.paged_ssm_update(*a, order="dbx", mode="pallas"),
        ((SLOTS, S, R), F32), ((SLOTS, S, R), F32),
        ((SLOTS, S, ds), F32), ((SLOTS, S, ds), F32),
        ((R, ds), F32), ((64, R, ds), F32),
        ((SLOTS,), I32), ((SLOTS,), I32),
        ((SLOTS, W), I32), ((SLOTS, W), I32), ((SLOTS,), I32))
    assert "tpu_custom_call" in hlo


def test_flash_attention_compiles_at_gpt2_widths(one_chip):
    # gpt2_nanogpt: 12 heads of 64 over a 1024-token context, batch 8
    shape = ((8, 12, 1024, 64), BF16)
    hlo = _compile(
        one_chip,
        lambda q, k, v: fa.flash_attention_bhsd(q, k, v, causal=True,
                                                interpret=False),
        shape, shape, shape)
    assert "tpu_custom_call" in hlo


def test_rmsnorm_compiles_at_qwen3_width(one_chip):
    hlo = _compile(one_chip,
                   lambda x, w: rn.rmsnorm_2d(x, w, interpret=False),
                   ((8192, 2048), BF16), ((2048,), F32))
    assert "tpu_custom_call" in hlo
