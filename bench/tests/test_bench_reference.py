"""The plain reference against the program, at small sizes on the CPU:
the same weights from the same seed, bit for bit, and the same logits
when the program computes in float32."""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_tiny  # noqa: E402
from benchlib import program, reference  # noqa: E402


@pytest.mark.parametrize("make", [bench_tiny.olmo, bench_tiny.phi4])
def test_reference_draws_the_programs_weights(make):
    import jax

    from repro.models.model import build_model

    cfg = make()
    params = build_model(program.model_config(cfg)).init_params(
        jax.random.PRNGKey(123))
    ref = reference.all_weights(cfg, 123)
    np.testing.assert_array_equal(np.asarray(params["embed"], np.float32),
                                  np.asarray(ref["embed"]))
    for i, lp in enumerate(ref["layers"]):
        for block in ("attn", "mlp"):
            for name, w in lp[block].items():
                got = np.asarray(params["layers"][block][name][i],
                                 np.float32)
                np.testing.assert_array_equal(got, np.asarray(w))


@pytest.mark.parametrize("make", [bench_tiny.olmo, bench_tiny.phi4])
def test_reference_logits_match_the_program_in_float32(make):
    import jax
    import jax.numpy as jnp

    from repro.models.model import build_model

    cfg = dict(make(), param_dtype="float32")
    mc = dataclasses.replace(program.model_config(cfg),
                             compute_dtype="float32")
    model = build_model(mc)
    params = model.init_params(jax.random.PRNGKey(7))
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], (2, 24))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(model.logits_fn(params, jnp.asarray(ids)))
    w = reference.all_weights(cfg, 7)
    x = w["embed"][ids]
    for lp in w["layers"]:
        x = reference.layer(cfg, lp, x, "float32")
    got = np.asarray(reference.logits(
        w["embed"], reference.norm(cfg, w["final_norm"], x), "float32"))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_fp8_control_moves_the_logits_more_than_bfloat16():
    import jax.numpy as jnp

    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.normal(size=(64, 256)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(256, 64)) / 16, jnp.float32)
    exact = np.asarray(reference.einsum("ik,kj->ij", a, b, "float32"))
    bf16 = np.asarray(jnp.einsum("ik,kj->ij", a.astype(jnp.bfloat16),
                                 b.astype(jnp.bfloat16),
                                 preferred_element_type=jnp.float32))
    fp8 = np.asarray(reference.einsum("ik,kj->ij", a, b, "fp8"))
    e16 = np.abs(bf16 - exact).max()
    e8 = np.abs(fp8 - exact).max()
    assert e8 > 4 * e16 > 0
