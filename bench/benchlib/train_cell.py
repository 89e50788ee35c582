"""A training cell: ``repro.launch.train.train`` itself, timed and checked.

The harness hands ``train()`` its own arguments and wraps the step that
``launch/steps.py::build_train_step`` returns; ``train()``'s loop, its
loader, ``device_put``, loss read-back and straggler monitor run as
they always do. Through that one object the first ``CHECKED_STEPS``
steps run from the seed on rows that all differ, and the wrapper keeps
what the comparison needs: those steps' rows and losses, the first
gradient as AdamW took it (from the first moment after step 1), and the
parameters' change over the checked steps (as step 4 receives them).
The window opens at the completion of step ``WARM_STEPS`` and closes
by raising from the wrapper, which ends the loop before ``train()``
saves its final checkpoint. Then the program's state is freed and the
float32 reference that the configuration names (``spec.reference``)
runs the checked steps on the same rows.
"""
from __future__ import annotations

import gc
import math
import shutil
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

from benchlib import check, device, program, spec
from benchlib.window import Window, WindowClosed

CHECKED_STEPS = 3
WARM_STEPS = CHECKED_STEPS + 2


def _leaf_name(path) -> str:
    return ".".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def leaf_norms(tree, scale: float = 1.0) -> Dict[str, float]:
    """Per-leaf norms of the program's parameter tree, the stacked layer
    leaves split per layer (``layers.<i>.<block>.<name>``), as the
    reference names them."""
    import jax
    import jax.numpy as jnp

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    names = [_leaf_name(p) for p, _ in flat]

    @jax.jit
    def norms(leaves):
        out = []
        for name, x in zip(names, leaves):
            x = x.astype(jnp.float32)
            axes = tuple(range(1 if name.startswith("layers.") else 0,
                               x.ndim))
            out.append(jnp.sqrt(jnp.sum(x * x, axis=axes)))
        return out

    return _named(names, [np.asarray(v) * scale
                          for v in norms([x for _, x in flat])])


def _named(names, values) -> Dict[str, float]:
    out = {}
    for name, v in zip(names, values):
        if name.startswith("layers."):
            block = name[len("layers."):]
            for i, x in enumerate(np.atleast_1d(v)):
                out[f"layers.{i}.{block}"] = float(x)
        else:
            out[name] = float(v)
    return out


def host_delta_norms(before, after) -> Dict[str, float]:
    import jax

    fb, _ = jax.tree_util.tree_flatten_with_path(before)
    fa = jax.tree.leaves(after)
    names, values = [], []
    for (path, b), a in zip(fb, fa):
        name = _leaf_name(path)
        d = np.asarray(a, np.float64) - np.asarray(b, np.float64)
        if name.startswith("layers."):
            values.append(np.sqrt(np.sum(
                d.reshape(d.shape[0], -1) ** 2, axis=1)))
        else:
            values.append(np.sqrt(np.sum(d * d)))
        names.append(name)
    return _named(names, values)


class TrainRecorder:
    """Wraps the program's train step; see the module docstring."""

    def __init__(self, window: Window, opt: Dict[str, Any],
                 tokens_per_step: int):
        self.window = window
        self.opt = opt
        self.tokens_per_step = tokens_per_step
        self.calls = 0
        self.batches: List[Dict[str, np.ndarray]] = []
        self.losses: List[float] = []
        self.first_grad: Dict[str, float] = {}
        self.delta: Dict[str, float] = {}
        self.rows_by_device: Dict[int, int] = {}
        self.window_steps = 0
        self.window_failed = 0
        self.traced_steps = 0
        self._p0 = None

    def wrap(self, step_fn):
        import jax

        def step(state, batch):
            self.calls += 1
            k = self.calls
            if k == 1:
                self.rows_by_device = {
                    s.device.id: int(s.data.shape[0])
                    for s in batch["inputs"].addressable_shards}
                self._p0 = jax.device_get(state.params)
            if k <= CHECKED_STEPS:
                self.batches.append({n: np.asarray(jax.device_get(a))
                                     for n, a in batch.items()})
            if k == 2:
                self.first_grad = leaf_norms(
                    state.opt.m, 1.0 / (1.0 - self.opt["betas"][0]))
            if k == CHECKED_STEPS + 1:
                self.delta = host_delta_norms(
                    self._p0, jax.device_get(state.params))
                self._p0 = None
            with jax.profiler.TraceAnnotation("bench.train_step"):
                out = jax.block_until_ready(step_fn(state, batch))
            t = time.perf_counter()
            loss = float(out[1]["loss"])
            if k <= CHECKED_STEPS:
                self.losses.append(loss)
            if k == WARM_STEPS:
                self.window.start(t)
            elif self.window.open:
                self.window_steps += 1
                self.window_failed += 0 if math.isfinite(loss) else 1
                if self.window.tracing:
                    self.traced_steps += 1
                self.window.tick(t)
            return out

        return step


def check_optimizer(tcfg, opt: Dict[str, Any]) -> None:
    o = tcfg.optimizer
    got = {"name": o.name, "lr": o.lr, "betas": list(o.betas),
           "eps": o.eps, "weight_decay": o.weight_decay,
           "grad_clip": o.grad_clip, "schedule": o.schedule,
           "warmup_steps": o.warmup_steps}
    if got != opt:
        raise SystemExit(f"bench: the program's optimizer {got} is not "
                         f"the traffic file's {opt}")


def run(cfg: Dict, mix: Dict, seed: int, devs, work: Path,
        window: Window, limits: Optional[Dict[str, float]],
        controls=(), faults=()) -> Dict[str, Any]:
    """Run the cell; returns the program's numbers, the checks, and what
    the per-layer readers need. ``controls`` (lower precisions of the
    reference in the program's place) and ``faults`` are read by the
    control script only."""
    from repro.launch import steps as steps_mod
    from repro.launch import train as train_mod

    mc = program.model_config(cfg)
    opt = mix["optimizer"]
    s31 = program.seed31(seed)
    argv = ["--arch", cfg["program_arch"], "--steps", "100000000",
            "--global-batch", str(mix["global_batch"]),
            "--seq-len", str(mix["seq_len"]), "--devices", mix["devices"],
            "--grad-reduction", mix["grad_reduction"],
            "--bucket-mb", str(mix["bucket_mb"]),
            "--optimizer", opt["name"], "--lr", repr(opt["lr"]),
            "--warmup", str(opt["warmup_steps"]),
            "--schedule", opt["schedule"], "--seed", str(s31),
            "--log-every", "100000000",
            "--replan-interval", "100000000",
            "--data-dir", str(work / "data"),
            "--ckpt-dir", str(work / "ckpt")]
    if mix.get("capacities"):
        argv += ["--capacities", mix["capacities"]]
    rec = TrainRecorder(window, opt,
                        mix["global_batch"] * mix["seq_len"])
    build = steps_mod.build_train_step

    def wrapped_build(model, tcfg, mesh):
        check_optimizer(tcfg, opt)
        return rec.wrap(build(model, tcfg, mesh))

    steps_mod.build_train_step = wrapped_build
    try:
        train_mod.train(train_mod.parse_args(argv), mc)
    except WindowClosed:
        pass
    finally:
        steps_mod.build_train_step = build
        if window.tracing:
            window.stop_trace(time.perf_counter())
        shutil.rmtree(work, ignore_errors=True)
    if window.t_close is None:
        raise RuntimeError("train() returned before the window closed")
    memory = device.memory_peak_bytes(devs)
    gc.collect()

    prog = {"losses": rec.losses, "first_grad": rec.first_grad,
            "delta": rec.delta}
    reference = spec.reference(cfg)
    t_ref = time.perf_counter()
    ref = reference.train_reference(cfg, opt, s31, rec.batches,
                                    devices=devs)
    readings = {"program": check.train_numbers(prog, ref),
                "losses": {"program": rec.losses,
                           "reference": ref["losses"]},
                "reference_s": time.perf_counter() - t_ref}
    for p in controls:
        readings[p] = check.train_numbers(reference.train_reference(
            cfg, opt, s31, rec.batches, precision=p, devices=devs), ref)
    for fault in faults:
        readings[fault] = check.train_numbers(
            fault_run(cfg, opt, s31, rec.batches, devs, fault), ref)
    rows = [np.flatnonzero(b["weights"].sum(axis=1) > 0)
            for b in rec.batches]
    sound = (all(len(r) == mix["global_batch"] for r in rows)
             and distinct_rows(rec.batches, rows))
    checks = check.judge(readings["program"], limits) if limits else {}
    return {
        "correct": bool(checks) and sound and check.passes(checks),
        "stand_ins": check.judge_stand_ins(
            readings, tuple(controls) + tuple(faults), limits),
        "attempted": rec.window_steps, "failed": rec.window_failed,
        "setup_s": window.setup_s, "window_s": window.window_s,
        "tokens": rec.window_steps * rec.tokens_per_step,
        "traced_tokens": rec.traced_steps * rec.tokens_per_step,
        "rows_by_device": rec.rows_by_device,
        "memory_peak_bytes": memory, "checks": checks,
        "readings": readings, "sound_rows": sound,
    }


def distinct_rows(batches, rows) -> bool:
    seen = set()
    for b, r in zip(batches, rows):
        for i in r:
            key = b["inputs"][i].tobytes()
            if key in seen:
                return False
            seen.add(key)
    return True


def fault_run(cfg, opt, s31, batches, devs, fault: str) -> Dict:
    """The reference in the program's place with one planted fault: half
    of each batch's rows left out and the mean taken over the rest, or
    (several chips) the exchange left out, so the first rank's rows
    alone make the update."""
    rows = []
    for b in batches:
        real = np.flatnonzero(b["weights"].sum(axis=1) > 0)
        if fault == "half_batch":
            rows.append(real[:len(real) // 2])
        elif fault == "no_exchange":
            per_rank = b["weights"].shape[0] // len(devs)
            rows.append(real[real < per_rank])
        else:
            raise ValueError(fault)
    return spec.reference(cfg).train_reference(cfg, opt, s31, batches,
                                               devices=devs, rows_of=rows)
