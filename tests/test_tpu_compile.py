"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Interpret-mode parity (tests/test_paged_kernels.py, tests/test_kernels.py)
cannot see what the chip's compiler refuses: more than one batch dim in
an in-kernel matmul, tiles not aligned to the (8, 128) layout, scratch
beyond scoped VMEM. Here each kernel is lowered and compiled by the TPU
compiler for a *described* (not attached) v5e chip, at the widths of the
models the serving and training paths run. Nothing executes.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time, and it keeps the
library until it exits.
"""
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding
from jaxlib._jax import HloPrintOptions

from repro.kernels.flash_attention.flash_attention import (
    flash_attention_pallas, flash_decode_paged_pallas)
from repro.kernels.mla_decode.mla_decode import mla_decode_paged_pallas
from repro.kernels.quantize.quantize import (dequant_accum_pallas,
                                             quantize_int8_pallas)


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # pragma: no cover
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_paged_gqa_decode_compiles_at_tinyllama_widths(one_chip):
    # tinyllama-1.1b: 32 query heads over 4 kv heads, head dim 64;
    # 8 decode slots, 16-token blocks, a 2048-token window
    b, h, hkv, d, bs, mb = 8, 32, 4, 64, 16, 128
    n_pool = b * mb
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = _compile(
        flash_decode_paged_pallas,
        s((b, 1, h, d), jnp.bfloat16),
        s((n_pool, bs, hkv, d), jnp.bfloat16),
        s((n_pool, bs, hkv, d), jnp.bfloat16),
        s((b, mb), jnp.int32), s((b,), jnp.int32))
    assert compiled.memory_analysis() is not None


def test_paged_gqa_decode_compiles_at_phi4_mini_widths(one_chip):
    # phi4-mini, as the serving benchmark runs it: 24 query heads over 8
    # kv heads of 128, 16 decode slots, 16-token blocks, a 2048-token
    # window over a 2048-block pool
    b, h, hkv, d, bs, mb, n_pool = 16, 24, 8, 128, 16, 128, 2048
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = _compile(
        flash_decode_paged_pallas,
        s((b, 1, h, d), jnp.bfloat16),
        s((n_pool, bs, hkv, d), jnp.bfloat16),
        s((n_pool, bs, hkv, d), jnp.bfloat16),
        s((b, mb), jnp.int32), s((b,), jnp.int32))
    # the roofline metric finds the kernel in the device trace by its HLO
    # line, operand shapes included: one query per slot out, the block
    # table as the first operand
    bench = Path(__file__).resolve().parents[1] / "bench"
    sys.path.insert(0, str(bench))
    from benchlib import spec
    kernel = spec.metric_reader("paged_attn_roofline.serve").KERNEL
    shapes = HloPrintOptions()
    shapes.print_operand_shape = True
    text = compiled.runtime_executable().hlo_modules()[0].to_string(shapes)
    assert re.search(kernel, text), [
        line for line in text.splitlines() if "custom-call" in line]


def test_flash_prefill_compiles_at_olmo_widths(one_chip):
    # olmo-1b: 16 heads of 128, a 2048-token causal prefill
    b, seq, h, d = 1, 2048, 16, 128
    q = jax.ShapeDtypeStruct((b, seq, h, d), jnp.bfloat16,
                             sharding=one_chip)
    _compile(lambda q, k, v: flash_attention_pallas(q, k, v, causal=True),
             q, q, q)


def test_paged_mla_decode_compiles_at_deepseek_v2_widths(one_chip):
    # deepseek-v2: 128 heads, latent rank 512, rope head dim 64
    b, h, r, dr, bs, mb = 8, 128, 512, 64, 16, 128
    n_pool = b * mb
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    _compile(
        lambda qa, qr, ckv, kr, t, n: mla_decode_paged_pallas(
            qa, qr, ckv, kr, t, n, (128 + dr) ** -0.5),
        s((b, h, r), jnp.bfloat16), s((b, h, dr), jnp.bfloat16),
        s((n_pool, bs, r), jnp.bfloat16), s((n_pool, bs, dr), jnp.bfloat16),
        s((b, mb), jnp.int32), s((b,), jnp.int32))


def test_int8_bucket_kernels_compile_on_a_4mib_bucket(one_chip):
    # one 4 MiB f32 gradient bucket in 256-element blocks; the receive
    # side sums 4 ranks' int8 shards
    elems, block, ranks = (4 << 20) // 4, 256, 4
    blocks = elems // block
    _compile(lambda x: quantize_int8_pallas(x, block_size=block),
             jax.ShapeDtypeStruct((elems,), jnp.float32, sharding=one_chip))
    _compile(dequant_accum_pallas,
             jax.ShapeDtypeStruct((ranks, blocks, block), jnp.int8,
                                  sharding=one_chip),
             jax.ShapeDtypeStruct((ranks, blocks), jnp.float32,
                                  sharding=one_chip))
