"""README quickstart smoke — documented commands must stay runnable.

Parses every fenced code block in README.md, extracts the documented
``repro.launch.train`` invocations (joining backslash continuations),
and executes each one in ``--dry-run`` form: the driver builds the
mesh, capacity plan and full config stack and runs the same validation
``build_train_step`` does, then exits before compiling anything. A
renamed CLI flag, a removed config mode, or a documented-but-invalid
config combination fails the ``benchmarks/run.py --quick`` tier
loudly instead of rotting in the docs.
"""
from __future__ import annotations

import os
import re
import subprocess
import sys
from typing import List

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
README = os.path.join(REPO, "README.md")
_TRAIN_MODULE = "repro.launch.train"


def quickstart_commands(readme_path: str = README) -> List[List[str]]:
    """Documented train-driver invocations, one token list each."""
    with open(readme_path) as fh:
        text = fh.read()
    blocks = re.findall(r"```[a-z]*\n(.*?)```", text, flags=re.S)
    commands: List[List[str]] = []
    for block in blocks:
        # join backslash-continued lines before tokenizing
        joined = re.sub(r"\\\s*\n\s*", " ", block)
        for line in joined.splitlines():
            line = line.strip()
            if _TRAIN_MODULE not in line or line.startswith("#"):
                continue
            tokens = line.split()
            args = tokens[tokens.index(_TRAIN_MODULE) + 1:]
            commands.append(args)
    return commands


def run_docs_smoke(readme_path: str = README) -> int:
    """Execute every quickstart command with ``--dry-run``; returns the
    number of commands checked. Raises on the first failure."""
    commands = quickstart_commands(readme_path)
    if not commands:
        raise SystemExit(
            f"docs smoke: no '{_TRAIN_MODULE}' commands found in "
            f"{readme_path} — the README quickstart must document at "
            f"least one runnable invocation")
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(REPO, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    env.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    # dry runs are a CPU tier; never contend with a parent for a chip
    env["JAX_PLATFORMS"] = "cpu"
    for args in commands:
        argv = [sys.executable, "-m", _TRAIN_MODULE] + args
        if "--dry-run" not in args:
            argv.append("--dry-run")
        proc = subprocess.run(argv, capture_output=True, text=True,
                              env=env, cwd=REPO, timeout=600)
        if proc.returncode != 0:
            raise SystemExit(
                f"docs smoke: README command failed "
                f"(exit {proc.returncode}):\n  {' '.join(argv)}\n"
                f"{proc.stderr[-2000:]}")
    return len(commands)


if __name__ == "__main__":
    n = run_docs_smoke()
    print(f"[docs_smoke] {n} README quickstart command(s) ok")
