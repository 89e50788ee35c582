"""``steps.train_schedule``: which schedule each config cell runs, and
the state layout that follows from it, checked against the state the
cell actually lays out (``state_shapes``, ``checkpoint_format``).

The cases need a (pod, data, model) mesh, so one child process with 8
emulated devices decides them all (only children force device counts);
each case is then a test of its own.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

B, H = "bucketed_allreduce", "hierarchical"
# name: (mesh, HetConfig kwargs, kind, packed moments, error state)
CASES = {
    "allreduce": ("pod", {}, "allreduce", False, None),
    "bucketed_allreduce": ("pod", dict(grad_reduction=B, bucket_mb=0.05),
                           "bucketed_allreduce", False, None),
    "hierarchical_flat_err": (
        "pod", dict(grad_reduction=H, compression="int8", bucket_mb=0.05),
        "hierarchical", False, "flat"),
    "hierarchical_per_leaf_err": (
        "pod", dict(grad_reduction=H, compression="int8"),
        "hierarchical", False, "per_leaf"),
    "overlap_buckets": (
        "pod", dict(grad_reduction=H, compression="int8", bucket_mb=0.05,
                    overlap="buckets"),
        "overlap_buckets", True, "flat"),
    "overlap_backward": (
        "pod", dict(grad_reduction=B, bucket_mb=0.05, overlap="backward"),
        "overlap_backward", True, None),
    "pipeline": ("pod", dict(pipeline_stages=2, accum_steps=2),
                 "pipeline", False, None),
    "canonical": ("pod", dict(weighting="canonical"), "canonical", False,
                  None),
    # the fallbacks: hierarchical without a "pod" axis runs the plain
    # schedule, and so does its overlap, which has no reduction axes
    # left; overlap over a DP-less mesh runs its reduction's schedule
    "fallback_hierarchical_without_pod": (
        "data", dict(grad_reduction=H, compression="int8"),
        "allreduce", False, None),
    "fallback_overlap_hierarchical_without_pod": (
        "data", dict(grad_reduction=H, compression="int8", bucket_mb=0.05,
                     overlap="buckets"),
        "allreduce", False, None),
    "fallback_overlap_without_dp_axes": (
        "model", dict(grad_reduction=B, bucket_mb=0.05, overlap="buckets"),
        "bucketed_allreduce", False, None),
}

CHILD = """
    import json, sys
    import jax
    from repro.configs import base
    from repro.configs.base import HetConfig, TrainConfig
    from repro.launch import steps
    from repro.launch.mesh import make_mesh
    from repro.models.model import build_model

    meshes = {"pod": make_mesh((2, 2, 2), ("pod", "data", "model")),
              "data": make_mesh((4, 2), ("data", "model")),
              "model": make_mesh((8,), ("model",))}
    model = build_model(base.smoke_config("olmo-1b"))

    def err_layout(err):
        if err == ():
            return None
        return ("flat" if isinstance(err, jax.ShapeDtypeStruct)
                else "per_leaf")

    out = {}
    for name, (mesh, het) in json.loads(sys.argv[1]).items():
        tcfg = TrainConfig(model=model.cfg, het=HetConfig(**het))
        sched = steps.train_schedule(model, tcfg, meshes[mesh])
        shapes = steps.state_shapes(model, tcfg, meshes[mesh])
        fmt = steps.checkpoint_format(model, tcfg, meshes[mesh])
        out[name] = {
            "kind": sched.kind, "packed": sched.packed, "err": sched.err,
            "stages": sched.splan is not None,
            "shapes_packed": isinstance(shapes.opt.m,
                                        jax.ShapeDtypeStruct),
            "shapes_err": err_layout(shapes.err),
            "format_state": fmt["state"],
            "format_stages": fmt["pipeline"] is not None}
    print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def decided():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    arg = json.dumps({n: (c[0], c[1]) for n, c in CASES.items()})
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(CHILD), arg],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("name", sorted(CASES))
def test_schedule_and_state_layout(decided, name):
    _, _, kind, packed, err = CASES[name]
    got = decided[name]
    assert got["kind"] == kind
    assert got["packed"] == got["shapes_packed"] == packed
    assert got["format_state"] == ("packed" if packed else "pytree")
    assert got["err"] == got["shapes_err"] == err
    assert got["stages"] == got["format_stages"] == (kind == "pipeline")


def test_cases_cover_every_schedule():
    from repro.launch.steps import SCHEDULES

    assert sorted({c[2] for c in CASES.values()}) == sorted(SCHEDULES)
