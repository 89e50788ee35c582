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
import dataclasses
import functools
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding
from jaxlib._jax import HloPrintOptions

from repro import compat
from repro.kernels.flash_attention.flash_attention import (
    flash_attention_pallas, flash_decode_paged_pallas)
from repro.kernels.mla_decode.mla_decode import mla_decode_paged_pallas
from repro.kernels.quantize.quantize import (dequant_accum_pallas,
                                             quantize_int8_pallas)
from repro.roofline.hlo import pool_move_bytes


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


def _paged_kernel_regex():
    """The pattern by which the roofline metric finds the paged decode
    kernel in the device trace (its HLO line, operand shapes included)."""
    bench = Path(__file__).resolve().parents[1] / "bench"
    sys.path.insert(0, str(bench))
    from benchlib import spec
    return spec.metric_reader("paged_attn_roofline.serve").KERNEL


def _hlo_with_operand_shapes(compiled) -> str:
    shapes = HloPrintOptions()
    shapes.print_operand_shape = True
    return compiled.runtime_executable().hlo_modules()[0].to_string(shapes)


def test_paged_gqa_decode_compiles_at_tinyllama_widths(one_chip):
    # tinyllama-1.1b: 22 layers, 32 query heads over 4 kv heads, head
    # dim 64; 8 decode slots, 16-token blocks, a 2048-token window
    b, h, hkv, d, bs, mb, layers = 8, 32, 4, 64, 16, 128, 22
    n_pool = b * mb
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = _compile(
        flash_decode_paged_pallas,
        s((b, 1, h, d), jnp.bfloat16),
        s((layers, n_pool, bs, hkv, d), jnp.bfloat16),
        s((layers, n_pool, bs, hkv, d), jnp.bfloat16),
        s((b, mb), jnp.int32), s((b,), jnp.int32), s((), jnp.int32))
    assert compiled.memory_analysis() is not None


def test_paged_gqa_decode_compiles_at_phi4_mini_widths(one_chip):
    # phi4-mini, as the serving benchmark runs it: 32 layers, 24 query
    # heads over 8 kv heads of 128, 16 decode slots, 16-token blocks, a
    # 2048-token window over a 2048-block pool
    b, h, hkv, d, bs, mb, n_pool, layers = 16, 24, 8, 128, 16, 128, 2048, 32
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = _compile(
        flash_decode_paged_pallas,
        s((b, 1, h, d), jnp.bfloat16),
        s((layers, n_pool, bs, hkv, d), jnp.bfloat16),
        s((layers, n_pool, bs, hkv, d), jnp.bfloat16),
        s((b, mb), jnp.int32), s((b,), jnp.int32), s((), jnp.int32))
    # the roofline metric finds the kernel in the device trace by its HLO
    # line, operand shapes included: one query per slot out, the block
    # table as the first operand
    text = _hlo_with_operand_shapes(compiled)
    assert re.search(_paged_kernel_regex(), text), [
        line for line in text.splitlines() if "custom-call" in line]


def test_paged_decode_step_carries_the_pool_in_place(one_chip, monkeypatch):
    """The whole paged decode step at phi4-mini's widths (4 of its 32
    layers): the layer scan carries the pool, so no op copies or slices
    a layer's pool slab or more, the pool output aliases the donated
    input, ``serve.pool_move_bytes``'s walker reads 0, and the roofline
    metric still finds the kernel."""
    from repro.configs import base as cfgbase
    from repro.launch import steps as steps_mod
    from repro.launch.mesh import make_mesh
    from repro.models.kvcache import PagedLayout
    from repro.models.model import build_model

    # the kernel is compiled for the described chip, not interpreted
    monkeypatch.setattr(compat, "pallas_interpret_fallback",
                        lambda what: False)
    cfg = dataclasses.replace(cfgbase.resolve("phi4-mini-3.8b"),
                              num_layers=4, param_dtype="bfloat16",
                              compute_dtype="bfloat16",
                              attention_impl="pallas")
    model = build_model(cfg)
    slots, bs, n_pool, mb = 16, 16, 2048, 128
    layout = PagedLayout(block_size=bs, num_blocks=n_pool,
                         max_blocks_per_seq=mb)
    mesh = make_mesh((1, 1), ("data", "model"),
                     devices=[one_chip.device_set.pop()])
    step = steps_mod.build_paged_decode_step(model, mesh, layout, slots)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    cache = jax.eval_shape(functools.partial(model.init_paged_cache, layout))
    i32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    with jax.set_mesh(mesh):
        compiled = step.lower(params, i32((slots,)), cache,
                              i32((slots, mb)), i32((slots,))).compile()
    text = compiled.as_text()

    slab = n_pool * bs * cfg.num_kv_heads * cfg.head_dim * 2
    moved = []
    for line in text.splitlines():
        m = re.search(r"= (\w+)\[([\d,]*)\]\S* (copy|dynamic-slice)\(",
                      line)
        if m and m.group(1) == "bf16" and 2 * np.prod(
                [int(x) for x in m.group(2).split(",") if x]) >= slab:
            moved.append(line.strip()[:160])
    assert not moved, moved
    pool_bytes = cfg.num_layers * slab
    assert compiled.memory_analysis().alias_size_in_bytes == 2 * pool_bytes
    aliased = re.search(r"input_output_alias=\{([^\n]*?)\}, entry", text)
    assert aliased and "{1}:" in aliased.group(1) \
        and "{2}:" in aliased.group(1), text[:400]
    assert pool_move_bytes(
        text, [(n_pool, bs, cfg.num_kv_heads, cfg.head_dim)]) == 0
    assert re.search(_paged_kernel_regex(), _hlo_with_operand_shapes(compiled))


def test_flash_prefill_compiles_at_olmo_widths(one_chip):
    # olmo-1b: 16 heads of 128, a 2048-token causal prefill
    b, seq, h, d = 1, 2048, 16, 128
    q = jax.ShapeDtypeStruct((b, seq, h, d), jnp.bfloat16,
                             sharding=one_chip)
    _compile(lambda q, k, v: flash_attention_pallas(q, k, v, causal=True),
             q, q, q)


def test_paged_mla_decode_compiles_at_deepseek_v2_widths(one_chip):
    # deepseek-v2: 60 layers, 128 heads, latent rank 512, rope head dim 64
    b, h, r, dr, bs, mb, layers = 8, 128, 512, 64, 16, 128, 60
    n_pool = b * mb
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    _compile(
        lambda qa, qr, ckv, kr, t, n, l: mla_decode_paged_pallas(
            qa, qr, ckv, kr, t, n, l, (128 + dr) ** -0.5),
        s((b, h, r), jnp.bfloat16), s((b, h, dr), jnp.bfloat16),
        s((layers, n_pool, bs, r), jnp.bfloat16),
        s((layers, n_pool, bs, dr), jnp.bfloat16),
        s((b, mb), jnp.int32), s((b,), jnp.int32), s((), jnp.int32))


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
