"""End-to-end system tests: the CLI train/serve drivers (subprocess)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_cli(args, devices=4, timeout=600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-m"] + args,
                          capture_output=True, text=True, env=env,
                          timeout=timeout, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return proc.stdout


@pytest.mark.slow
def test_train_driver_heterogeneous(tmp_path):
    """Full pipeline: synthetic shards -> het plan (one dead rank) ->
    prefetch -> SPMD step -> checkpoint; loss must decrease."""
    out = run_cli([
        "repro.launch.train", "--arch", "olmo-1b", "--smoke",
        "--steps", "25", "--global-batch", "16", "--seq-len", "48",
        "--capacities", "2,1,1,0", "--devices", "4,1",
        "--log-every", "10", "--ckpt-every", "20",
        "--ckpt-dir", str(tmp_path / "ckpt"),
        "--data-dir", str(tmp_path / "data"),
    ])
    assert "plan rows" in out
    lines = [l for l in out.splitlines() if l.startswith("[train] done")]
    assert lines, out
    first, last = [float(x) for x in
                   lines[0].split("loss")[1].strip().split(" -> ")]
    assert last < first
    # checkpoint rotation happened
    assert any(p.startswith("step_") for p in
               os.listdir(tmp_path / "ckpt"))


@pytest.mark.slow
def test_train_driver_resume(tmp_path):
    run_cli([
        "repro.launch.train", "--arch", "tinyllama-1.1b", "--smoke",
        "--steps", "10", "--global-batch", "8", "--seq-len", "32",
        "--devices", "2,2", "--ckpt-every", "10",
        "--ckpt-dir", str(tmp_path / "ckpt"),
        "--data-dir", str(tmp_path / "data")])
    out = run_cli([
        "repro.launch.train", "--arch", "tinyllama-1.1b", "--smoke",
        "--steps", "15", "--global-batch", "8", "--seq-len", "32",
        "--devices", "2,2", "--resume",
        "--ckpt-dir", str(tmp_path / "ckpt"),
        "--data-dir", str(tmp_path / "data")])
    assert "resumed from step 10" in out


@pytest.mark.slow
def test_serve_driver(tmp_path):
    """The continuous-batching serve driver end to end on a DP mesh
    with skewed pod speeds: every request completes and the engine
    reports the modeled throughput/latency stats."""
    out = run_cli([
        "repro.launch.serve", "--arch", "tinyllama-1.1b", "--smoke",
        "--slots", "4", "--prefill-batch", "2", "--requests", "8",
        "--max-prompt", "24", "--max-gen", "16",
        "--pod-speeds", "1,0.5", "--devices", "2,2"])
    assert "8 requests" in out
    assert "tok/unit" in out
    assert "decode steps" in out


@pytest.mark.slow
def test_train_driver_hierarchical_int8(tmp_path):
    out = run_cli([
        "repro.launch.train", "--arch", "olmo-1b", "--smoke",
        "--steps", "12", "--global-batch", "16", "--seq-len", "32",
        "--devices", "2,2,2", "--grad-reduction", "hierarchical",
        "--compression", "int8", "--accum", "2",
        "--ckpt-dir", str(tmp_path / "ckpt"),
        "--data-dir", str(tmp_path / "data")], devices=8)
    lines = [l for l in out.splitlines() if l.startswith("[train] done")]
    first, last = [float(x) for x in
                   lines[0].split("loss")[1].strip().split(" -> ")]
    assert last < first


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_chip_smoke_refuses_to_run_without_a_tpu(argv):
    """chip_smoke.py proves the entry points run on a chip: on the CPU
    it must fail, name the platform it found, and print no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")] + argv,
        capture_output=True, text=True, env=env, timeout=300, cwd=REPO)
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr, proc.stderr[-2000:]
    assert '"ok"' not in proc.stdout
