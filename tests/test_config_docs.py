"""Docs-drift guards: the README config matrix must match the code.

The README documents (a) the accepted values of every ``HetConfig``
mode knob and (b) the valid ``grad_reduction`` x ``overlap`` grid with
each cell's requirements. Both tables are parsed here and checked
against the actual validation behavior (``configs/base.py`` constants,
``HetConfig.validate``, ``launch/steps.py::validate_train_config``) so
a code change that isn't reflected in the docs — or a documented combo
the code rejects — fails CI. The quickstart flags are checked against
the train driver's argparse, and the checkpoint overlap-mode bugfix
(restore logs instead of silently adapting) is covered at the end.
"""
import dataclasses
import logging
import os
import re
import sys

import jax
import numpy as np
import pytest

from repro.configs import base as cfgs
from repro.configs.base import HetConfig, TrainConfig
from repro.launch.mesh import make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
README = os.path.join(REPO, "README.md")
if REPO not in sys.path:                      # for benchmarks.docs_smoke
    sys.path.insert(0, REPO)


def _tables(text):
    """All pipe tables as lists of row-cell lists (header first)."""
    tables, current = [], []
    for line in text.splitlines():
        if line.strip().startswith("|"):
            cells = [c.strip() for c in line.strip().strip("|").split("|")]
            if all(set(c) <= set("-: ") for c in cells):
                continue                      # separator row
            current.append(cells)
        elif current:
            tables.append(current)
            current = []
    if current:
        tables.append(current)
    return tables


@pytest.fixture(scope="module")
def readme_tables():
    with open(README) as fh:
        return _tables(fh.read())


def _find_table(tables, *header_needles):
    for t in tables:
        header = " ".join(t[0]).lower()
        if all(n in header for n in header_needles):
            return t
    raise AssertionError(
        f"README table with header containing {header_needles} not "
        f"found")


def test_readme_knob_values_match_constants(readme_tables):
    """The knob/values table lists EXACTLY the accepted mode values."""
    table = _find_table(readme_tables, "knob", "values")
    documented = {}
    for row in table[1:]:
        knob = row[0].strip("`")
        documented[knob] = [v.strip(" `") for v in row[1].split(",")]
    expected = {
        "grad_reduction": list(cfgs.GRAD_REDUCTION_MODES),
        "overlap": list(cfgs.OVERLAP_MODES),
        "compression": list(cfgs.COMPRESSION_MODES),
        "quantize_impl": list(cfgs.QUANTIZE_IMPLS),
        "weighting": list(cfgs.WEIGHTING_MODES),
        "pipeline_schedule": list(cfgs.PIPELINE_MODES),
        "attention_impl": list(cfgs.ATTENTION_IMPLS),
    }
    assert documented == expected, (
        f"README knob table out of sync with configs/base.py:\n"
        f"documented={documented}\nexpected={expected}")


def _combo_config(reduction, overlap, requirements):
    """Build (model_cfg, het) honoring a matrix row's requirements."""
    model = cfgs.smoke_config("olmo-1b")
    kwargs = {"grad_reduction": reduction, "overlap": overlap}
    if "bucket_mb" in requirements:
        kwargs["bucket_mb"] = 0.05
    if "scan_layers" in requirements:
        model = dataclasses.replace(model, scan_layers=False)
    return model, HetConfig(**kwargs)


def test_readme_matrix_rows_match_validation(readme_tables):
    """Every documented (grad_reduction, overlap) cell behaves as its
    'status' column claims — and the grid covers the full product."""
    from repro.launch.steps import validate_train_config
    from repro.models.model import build_model

    table = _find_table(readme_tables, "grad_reduction", "overlap",
                        "status")
    flat_mesh = make_mesh((1, 1), ("data", "model"))
    pod_mesh = make_mesh((1, 1, 1), ("pod", "data", "model"))
    seen = set()
    for row in table[1:]:
        reduction = row[0].strip("`")
        overlap = row[1].strip("`")
        requirements, status = row[2], row[3]
        assert reduction in cfgs.GRAD_REDUCTION_MODES, row
        assert overlap in cfgs.OVERLAP_MODES, row
        assert status in ("supported", "rejected"), row
        seen.add((reduction, overlap))
        # hierarchical reduces over the pod axis — its checks are only
        # live on a multi-pod-shaped mesh
        mesh = pod_mesh if reduction == "hierarchical" else flat_mesh
        model_cfg, het = _combo_config(reduction, overlap, requirements)
        model = build_model(model_cfg)
        tcfg = TrainConfig(model=model_cfg, het=het)
        if status == "supported":
            validate_train_config(model, tcfg, mesh)
            # each named requirement is real: dropping it must raise
            if "bucket_mb" in requirements:
                bad = dataclasses.replace(het, bucket_mb=0.0)
                with pytest.raises(ValueError, match="bucket_mb"):
                    validate_train_config(
                        model, TrainConfig(model=model_cfg, het=bad),
                        mesh)
            if "scan_layers" in requirements:
                scanned_cfg = dataclasses.replace(model_cfg,
                                                  scan_layers=True)
                scanned = build_model(scanned_cfg)
                with pytest.raises(ValueError, match="scan_layers"):
                    validate_train_config(
                        scanned,
                        TrainConfig(model=scanned_cfg, het=het), mesh)
        else:
            with pytest.raises(ValueError):
                validate_train_config(model, tcfg, mesh)
    full_grid = {(r, o) for r in cfgs.GRAD_REDUCTION_MODES
                 for o in cfgs.OVERLAP_MODES}
    assert seen == full_grid, (
        f"README matrix missing combos: {sorted(full_grid - seen)}")


def test_invalid_mode_values_raise():
    """Unknown values of every mode knob fail HetConfig.validate with
    a message naming the field."""
    for field, good in (("weighting", "tokens"),
                        ("grad_reduction", "allreduce"),
                        ("compression", "none"),
                        ("quantize_impl", "reference"),
                        ("overlap", "none")):
        with pytest.raises(ValueError, match=field):
            HetConfig(**{field: "bogus"}).validate()
    for field, bad, match in ((("bucket_mb"), -1.0, "bucket_mb"),
                              (("accum_steps"), 0, "accum_steps"),
                              (("straggler_ema"), 1.5, "straggler_ema"),
                              (("replan_interval"), 0,
                               "replan_interval"),
                              (("capacities"), (1.0, -2.0),
                               "capacities")):
        with pytest.raises(ValueError, match=match):
            HetConfig(**{field: bad}).validate()


def test_readme_chaos_presets_match_registry(readme_tables):
    """The README chaos-preset table lists EXACTLY the registered
    presets, and each row's fault kinds match what the preset builder
    actually schedules."""
    from repro.core import chaos

    table = _find_table(readme_tables, "preset", "faults")
    documented = {}
    for row in table[1:]:
        name = row[0].strip("`")
        documented[name] = {k.strip(" `") for k in row[1].split(",")}
    assert set(documented) == set(chaos.PRESETS), (
        f"README chaos table out of sync with core/chaos.py PRESETS: "
        f"documented={sorted(documented)} "
        f"registered={sorted(chaos.PRESETS)}")
    for name, build in chaos.PRESETS.items():
        actual = {ev.kind for ev in build(4, 2, 20)}
        assert documented[name] == actual, (
            f"preset {name!r}: README documents faults "
            f"{sorted(documented[name])}, builder schedules "
            f"{sorted(actual)}")


def test_readme_quickstart_flags_exist_in_train_cli():
    """Every flag the README documents is a real train.py option (the
    full --dry-run execution runs in benchmarks/run.py --quick)."""
    from benchmarks import docs_smoke
    from repro.launch import train as train_mod

    commands = docs_smoke.quickstart_commands(README)
    assert commands, "README quickstart documents no train commands"
    # collect the parser's option strings without running it
    import argparse
    real_flags = set()
    orig = argparse.ArgumentParser.parse_args
    try:
        argparse.ArgumentParser.parse_args = lambda self, *a, **k: (
            real_flags.update(o for action in self._actions
                              for o in action.option_strings),
            sys.exit(0))[1]
        with pytest.raises(SystemExit):
            train_mod.main()
    finally:
        argparse.ArgumentParser.parse_args = orig
    for args in commands:
        for tok in args:
            if tok.startswith("--"):
                assert tok in real_flags, (
                    f"README documents unknown flag {tok}; "
                    f"known: {sorted(real_flags)}")


def test_readme_pipeline_quickstart_documents_real_requirements():
    """The README must document a runnable --pipeline-stages command,
    and the requirements it demonstrates must be REAL: the documented
    flag set carries --no-scan-layers and --accum >= stages, and
    HetConfig.validate actually rejects a config missing them."""
    from benchmarks import docs_smoke

    commands = docs_smoke.quickstart_commands(README)
    pipe_cmds = [a for a in commands if "--pipeline-stages" in a]
    assert pipe_cmds, ("README quickstart documents no "
                       "--pipeline-stages command")
    for args in pipe_cmds:
        stages = int(args[args.index("--pipeline-stages") + 1])
        assert stages > 1, args
        assert "--no-scan-layers" in args, (
            "documented pipeline command must carry --no-scan-layers "
            "(the per-stage VJP segments need the unrolled stack)")
        assert "--accum" in args, args
        accum = int(args[args.index("--accum") + 1])
        assert accum >= stages, (
            f"documented pipeline command has --accum {accum} < "
            f"--pipeline-stages {stages}")
    # the documented requirements are enforced, not decorative
    with pytest.raises(ValueError, match="accum_steps"):
        HetConfig(pipeline_stages=2, accum_steps=1).validate()
    with pytest.raises(ValueError, match="overlap"):
        HetConfig(pipeline_stages=2, accum_steps=2, overlap="buckets",
                  grad_reduction="bucketed_allreduce",
                  bucket_mb=1.0).validate()


def test_readme_serve_flag_table_matches_serve_cli(readme_tables):
    """The serving section's flag table lists EXACTLY the serve
    driver's argparse options — a flag added/renamed in
    launch/serve.py without a README row (or vice versa) fails."""
    from repro.launch import serve as serve_mod

    table = _find_table(readme_tables, "flag", "default", "meaning")
    documented = {row[0].strip("`") for row in table[1:]}
    import argparse
    real_flags = set()
    orig = argparse.ArgumentParser.parse_args
    try:
        argparse.ArgumentParser.parse_args = lambda self, *a, **k: (
            real_flags.update(o for action in self._actions
                              for o in action.option_strings),
            sys.exit(0))[1]
        with pytest.raises(SystemExit):
            serve_mod.main()
    finally:
        argparse.ArgumentParser.parse_args = orig
    real_flags -= {"-h", "--help"}
    assert documented == real_flags, (
        f"README serve flag table out of sync with launch/serve.py:\n"
        f"documented-only={sorted(documented - real_flags)}\n"
        f"parser-only={sorted(real_flags - documented)}")


def test_attention_impl_knob_is_pinned_end_to_end():
    """``attention_impl`` (PR 9): the serve CLI's choices are EXACTLY
    ``ATTENTION_IMPLS``, ModelConfig rejects unknown values with a
    message naming the knob, and both docs surfaces — the README
    serving section and architecture.md §serving engine — document the
    flag and its loud interpret-mode fallback."""
    from repro.launch import serve as serve_mod

    import argparse
    choices = {}
    orig = argparse.ArgumentParser.parse_args
    try:
        argparse.ArgumentParser.parse_args = lambda self, *a, **k: (
            choices.update({o: action.choices
                            for action in self._actions
                            for o in action.option_strings}),
            sys.exit(0))[1]
        with pytest.raises(SystemExit):
            serve_mod.main()
    finally:
        argparse.ArgumentParser.parse_args = orig
    assert list(choices["--attention-impl"]) == list(
        cfgs.ATTENTION_IMPLS), (
        f"serve --attention-impl choices {choices['--attention-impl']} "
        f"!= configs/base.py ATTENTION_IMPLS {cfgs.ATTENTION_IMPLS}")

    with pytest.raises(ValueError, match="attention_impl"):
        dataclasses.replace(cfgs.smoke_config("olmo-1b"),
                            attention_impl="bogus")

    with open(README) as fh:
        readme = fh.read()
    assert "--attention-impl" in readme
    arch_md = os.path.join(REPO, "docs", "architecture.md")
    with open(arch_md) as fh:
        arch = fh.read()
    for doc, text in (("README.md", readme),
                      ("docs/architecture.md", arch)):
        assert "attention_impl" in text and "interpret" in text, (
            f"{doc} must document the attention_impl knob and its "
            f"loud interpret-mode fallback")


def test_label_smoothing_is_wired_through_the_train_step():
    """TrainConfig.label_smoothing is a LIVE knob (the docstring says
    so): it must reach the CE loss both via loss_fn and via
    build_train_step."""
    from repro.configs.base import OptimizerConfig, ShapeConfig
    from repro.launch import steps
    from repro.models.model import build_model

    model_cfg = dataclasses.replace(cfgs.smoke_config("olmo-1b"),
                                    compute_dtype="float32")
    model = build_model(model_cfg)
    params = jax.jit(model.init_params)(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    import jax.numpy as jnp
    batch = {
        "inputs": jnp.asarray(
            rng.integers(0, model_cfg.vocab_size, (2, 16)), jnp.int32),
        "labels": jnp.asarray(
            rng.integers(0, model_cfg.vocab_size, (2, 16)), jnp.int32),
        "weights": jnp.ones((2, 16), jnp.float32),
    }
    o0, _, _ = model.loss_fn(params, batch)
    o1, _, _ = model.loss_fn(params, batch, label_smoothing=0.2)
    assert float(o0) != float(o1), "label_smoothing kwarg is dead"

    mesh = make_mesh((1, 1), ("data", "model"))
    shape = ShapeConfig("t", 16, 2, "train")

    def one_loss(smoothing):
        tcfg = TrainConfig(model=model_cfg, shape=shape,
                           het=HetConfig(),
                           optimizer=OptimizerConfig(grad_clip=0.0),
                           label_smoothing=smoothing)
        with jax.set_mesh(mesh):
            state = steps.init_train_state(model, tcfg, mesh,
                                           jax.random.PRNGKey(0))
            step = steps.build_train_step(model, tcfg, mesh)
            _, met = step(state, batch)
        return float(met["loss"])

    assert one_loss(0.0) != one_loss(0.2), (
        "TrainConfig.label_smoothing does not reach the train step")
    with pytest.raises(ValueError, match="label_smoothing"):
        steps.validate_train_config(
            model, TrainConfig(model=model_cfg, label_smoothing=1.5),
            mesh)


def test_checkpoint_restore_logs_overlap_mode_mismatch(tmp_path,
                                                       caplog):
    """The checkpoint records which overlap mode wrote it, and restore
    LOGS a mismatch instead of silently adapting."""
    from repro.checkpoint import repack
    from repro.checkpoint.checkpoint import CheckpointManager

    state = {"w": np.arange(6, dtype=np.float32).reshape(2, 3)}
    fmt = {"version": repack.FORMAT_VERSION, "state": "pytree",
           "packed_fields": [], "layout": None, "overlap": "buckets"}
    mgr = CheckpointManager(str(tmp_path), keep=2)
    mgr.save(3, state, meta={"format": fmt}, block=True)

    with caplog.at_level(logging.WARNING,
                         logger="repro.checkpoint.checkpoint"):
        restored, meta = mgr.restore(state,
                                     expected_overlap="backward")
    np.testing.assert_array_equal(restored["w"], state["w"])
    assert meta["format"]["overlap"] == "buckets"
    assert any("overlap='buckets'" in r.message and
               "overlap='backward'" in r.message
               for r in caplog.records), caplog.records

    caplog.clear()
    with caplog.at_level(logging.WARNING,
                         logger="repro.checkpoint.checkpoint"):
        mgr.restore(state, expected_overlap="buckets")
    assert not caplog.records              # matching mode: no warning
