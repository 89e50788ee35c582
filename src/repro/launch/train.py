"""End-to-end heterogeneous training driver.

Wires every subsystem: synthetic/sharded data -> capacity plan ->
het sampler + prefetch loader -> jitted SPMD train step (weighted DP,
optional hierarchical/compressed reduction) -> straggler monitor ->
checkpointing -> elastic restart.

Elastic restart (core/elastic.py regime 2): when soft replanning cannot
absorb a membership change (``RemeshRequired``), the driver maps dead
DP ranks to lost pods, asks ``elastic.plan_remesh`` for the surviving
topology + capacity plan, rebuilds the mesh/step/loader, and restores
the latest checkpoint into the new layout — ``CheckpointManager.restore``
repacks packed optimizer state across bucket grids and mesh sizes
(checkpoint/repack.py), and ``elastic.validate_resume_equivalence``
verifies the old and new plans consume the identical global record
stream before training continues at the saved data-stream position.

Runs on anything: real TPU pods (production mesh) or this CPU container
(--devices data,model uses host devices; --smoke uses reduced configs).
Fault injection goes through the deterministic chaos engine
(core/chaos.py): ``--chaos <schedule.json|preset>`` scripts slowdowns,
rank/pod kills, flaky reports and checkpoint-IO failures, whose modeled
per-rank step times feed the straggler monitor (replacing the
undifferentiated host clock of single-process emulation) — slow ranks
shed rows via soft replans, dead ranks escalate to the elastic re-mesh.
``--kill-pod P@S`` is kept as a back-compat alias for a one-entry kill
schedule and exercises the full detect -> replan -> remesh ->
repacked-resume path end to end.

Example (CPU):
  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --smoke \
      --steps 50 --global-batch 16 --seq-len 64 \
      --capacities 2,1,1 --devices 4,1
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.checkpoint.checkpoint import CheckpointManager
from repro.configs import base as cfgbase
from repro.configs.base import (HetConfig, ModelConfig, OptimizerConfig,
                                ShapeConfig, TrainConfig)
from repro.core import capacity as cap
from repro.core import chaos, elastic
from repro.core.straggler import RemeshRequired, StragglerMonitor
from repro.data.dataset import ShardedDataset
from repro.data.loader import PrefetchLoader
from repro.data.sampler import HetSampler
from repro.data.synthetic import build_synthetic_corpus
from repro.launch import steps as steps_mod
from repro.launch.cache import use_compile_cache
from repro.launch.mesh import dp_size, make_mesh
from repro.launch.sharding import batch_specs, named
from repro.models.model import build_model


def build_everything(args, cfg: Optional[ModelConfig] = None):
    """``cfg``: the model configuration; ``None`` resolves ``--arch``
    (``--smoke`` for the reduced same-family config)."""
    if cfg is None:
        cfg = (cfgbase.smoke_config(args.arch) if args.smoke
               else cfgbase.resolve(args.arch))
    if getattr(args, "no_scan_layers", False):
        # unrolled layer stack — required by --overlap backward (the
        # staged layer-by-layer backward is an unrolled program)
        cfg = dataclasses.replace(cfg, scan_layers=False)
    model = build_model(cfg)

    dshape = tuple(int(x) for x in args.devices.split(","))
    n_needed = int(np.prod(dshape))
    if n_needed > len(jax.devices()):
        raise SystemExit(
            f"need {n_needed} devices, have {len(jax.devices())}; set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_needed}")
    axes = ("data", "model") if len(dshape) == 2 else ("pod", "data",
                                                       "model")
    mesh = make_mesh(dshape, axes)

    shape = ShapeConfig("cli", args.seq_len, args.global_batch, "train")
    tcfg = TrainConfig(
        model=cfg, shape=shape,
        het=HetConfig(
            capacities=tuple(float(c) for c in args.capacities.split(","))
            if args.capacities else (),
            weighting=args.weighting,
            grad_reduction=args.grad_reduction,
            compression=args.compression,
            bucket_mb=args.bucket_mb,
            overlap=args.overlap,
            accum_steps=args.accum,
            replan_interval=args.replan_interval,
            pipeline_stages=args.pipeline_stages,
            pipeline_schedule=args.pipeline_schedule),
        optimizer=OptimizerConfig(name=args.optimizer, lr=args.lr,
                                  warmup_steps=args.warmup,
                                  total_steps=args.steps,
                                  schedule=args.schedule),
        seed=args.seed, ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every)
    return cfg, model, mesh, tcfg


def make_plan(tcfg: TrainConfig, mesh) -> cap.CapacityPlan:
    n_dp = dp_size(mesh)
    caps = tcfg.het.capacities or tuple([1.0] * n_dp)
    if len(caps) != n_dp:
        raise SystemExit(f"--capacities needs {n_dp} entries (dp size)")
    return cap.plan_capacities(tcfg.shape.global_batch, caps,
                               headroom=1.25,
                               round_buffer_to=max(tcfg.het.accum_steps,
                                                   1))


def topology_from_mesh(mesh) -> elastic.MeshTopology:
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    return elastic.MeshTopology(pods=shape.get("pod", 1),
                                data_per_pod=shape.get("data", 1),
                                model=shape.get("model", 1))


def mesh_for_topology(topo: elastic.MeshTopology):
    """Mesh over the first N live devices (re-mesh uses a device subset
    — on a real fleet the coordinator would hand back the survivors)."""
    shape = topo.mesh_shape()
    n = int(np.prod(shape))
    if n > len(jax.devices()):
        raise SystemExit(f"re-mesh needs {n} devices, "
                         f"have {len(jax.devices())}")
    return make_mesh(shape, topo.mesh_axes(), jax.devices()[:n])


def _parse_kill(spec: str) -> Optional[Tuple[int, int]]:
    """'P@S' -> (pod P, from global step S). Back-compat alias: becomes
    a one-entry ``chaos.kill(pod=P, step=S)`` schedule."""
    if not spec:
        return None
    pod, at = spec.split("@")
    return int(pod), int(at)


def build_chaos_engine(args, tcfg: TrainConfig, mesh,
                       topo: elastic.MeshTopology) -> chaos.ChaosEngine:
    """Resolve --chaos (+ the --kill-pod alias) into one engine — the
    single fault-injection path for the driver."""
    n_dp = dp_size(mesh)
    schedule = chaos.ChaosSchedule(seed=tcfg.seed)
    if args.chaos:
        try:
            schedule = chaos.load_schedule(
                args.chaos, num_ranks=n_dp,
                data_per_pod=topo.data_per_pod,
                total_steps=args.steps, seed=tcfg.seed)
        except (ValueError, OSError) as e:
            raise SystemExit(f"[train] --chaos: {e}") from e
    kill = _parse_kill(args.kill_pod)
    if kill is not None:
        schedule = schedule.with_events(
            chaos.kill(pod=kill[0], step=kill[1]))
    try:
        return chaos.ChaosEngine(
            schedule, num_ranks=n_dp, data_per_pod=topo.data_per_pod,
            speeds=tcfg.het.capacities or None)
    except ValueError as e:
        raise SystemExit(f"[train] {e}") from e


def _batches(loader: PrefetchLoader, epoch: int, skip: int):
    """``(epoch, index in its epoch, raw batch)`` from ``epoch`` on, the
    first ``skip`` batches of it left out (a resume mid-epoch)."""
    while True:
        consumed = 0
        for raw in loader.iter_epoch(epoch):
            consumed += 1
            if consumed > skip:
                yield epoch, consumed, raw
        epoch += 1
        skip = 0


def _monitor(monitor: StragglerMonitor, engine: chaos.ChaosEngine,
             plan: cap.CapacityPlan, sampler: HetSampler, step: int,
             dt: float) -> cap.CapacityPlan:
    """Report the step's per-rank times and replan when due; returns the
    plan in force. On real fleets each host reports; here the chaos
    engine differentiates ranks from the host clock (slowdowns inflate,
    kills/flaky drop the report). No schedule => every rank reports the
    measured time."""
    monitor.observe(engine.step_times(step, plan.rows_per_rank, dt))
    if monitor.should_replan():
        new_plan = monitor.replan(plan)
        if new_plan.rows_per_rank.tolist() != plan.rows_per_rank.tolist():
            print(f"[train] replan: rows {plan.rows_per_rank.tolist()} -> "
                  f"{new_plan.rows_per_rank.tolist()}")
        plan = new_plan
        sampler.set_plan(plan)
    return plan


def train(args, cfg: Optional[ModelConfig] = None) -> Dict[str, Any]:
    """Run the training loop. ``cfg`` overrides ``--arch`` (see
    :func:`build_everything`).

    Returns ``steps`` and ``wall_s``; once a step ran, also
    ``first_loss``/``last_loss``, the per-step ``losses`` and wall
    times ``step_s`` (each step waited on with ``block_until_ready``),
    and ``batch_rows_by_device``: the rows of the first batch held by
    each device id, i.e. where the data-parallel shards landed.

    Each step is a ``train.iteration`` span (``repro/obs.py``) holding
    ``train.input`` (the loader's next batch, put on the devices),
    ``train.step`` (the step, waited on; its length is ``step_s``) and
    ``train.monitor`` (loss read-back, straggler report and replan, the
    log line, and ``train.checkpoint`` around a periodic save).
    """
    cfg, model, mesh, tcfg = build_everything(args, cfg)
    topo = topology_from_mesh(mesh)
    plan = make_plan(tcfg, mesh)
    print(f"[train] {cfg.name}: {cfg.param_count():,} params, mesh "
          f"{dict(zip(mesh.axis_names, mesh.devices.shape))}, plan rows "
          f"{plan.rows_per_rank.tolist()} buffer {plan.buffer_rows} "
          f"(efficiency {plan.efficiency():.2f})")
    # resolve fault injection before --dry-run exits so a documented
    # --chaos preset / schedule (and --kill-pod target) is validated by
    # the README docs smoke
    engine = build_chaos_engine(args, tcfg, mesh, topo)
    if engine.schedule.events:
        kinds = sorted({ev.kind for ev in engine.schedule.events})
        print(f"[train] chaos: {len(engine.schedule.events)} event(s) "
              f"{kinds} (seed {engine.schedule.seed})")
    if args.dry_run:
        # validate the full config stack (the same checks
        # build_train_step runs) and stop before any compilation or
        # data generation — the README quickstart smoke in
        # benchmarks/run.py --quick executes every documented command
        # this way, so a renamed flag or an invalid documented config
        # fails the quick tier loudly
        steps_mod.validate_train_config(model, tcfg, mesh)
        print(f"[train] dry-run ok: grad_reduction="
              f"{tcfg.het.grad_reduction} overlap={tcfg.het.overlap} "
              f"bucket_mb={tcfg.het.bucket_mb} "
              f"compression={tcfg.het.compression} "
              f"accum={tcfg.het.accum_steps} "
              f"optimizer={tcfg.optimizer.name} "
              f"scan_layers={cfg.scan_layers} "
              f"pipeline_stages={tcfg.het.pipeline_stages}")
        return {"steps": 0, "wall_s": 0.0}

    corpus = build_synthetic_corpus(
        args.data_dir, num_seqs=max(4 * plan.global_rows, 256),
        seq_len=args.seq_len + 1, vocab=cfg.vocab_size,
        rows_per_shard=64, seed=tcfg.seed)
    ds = ShardedDataset(corpus)
    mgr = CheckpointManager(tcfg.ckpt_dir, keep=tcfg.ckpt_keep,
                            fault_hook=engine.ckpt_fault_hook())

    def build_runtime(mesh, plan):
        """Everything that depends on the mesh / plan (rebuilt on
        re-mesh)."""
        with jax.set_mesh(mesh):
            step_fn = steps_mod.build_train_step(model, tcfg, mesh)
        canonical = tcfg.het.weighting == "canonical"
        sampler = HetSampler(ds, plan, seed=tcfg.seed,
                             canonical_order=canonical)
        loader = PrefetchLoader(sampler, depth=args.prefetch)
        # canonical batches are global-row-ordered (global_rows rows,
        # plan-independent); packed batches are rank-buffer-ordered
        # (padded_rows rows)
        batch_rows = plan.global_rows if canonical else plan.padded_rows
        bspecs = named(mesh, batch_specs(cfg, mesh, batch_rows))
        fmt = steps_mod.checkpoint_format(model, tcfg, mesh)
        return step_fn, sampler, loader, bspecs, fmt

    def restore_state(mesh, plan):
        """Repacked restore: the template carries THIS config's layout;
        the manager translates whatever the checkpoint holds into it."""
        template = steps_mod.state_shapes(model, tcfg, mesh)
        host, meta = mgr.restore(template,
                                 expected_overlap=tcfg.het.overlap)
        saved_plan = meta.get("plan")
        if saved_plan is not None and not \
                elastic.validate_resume_equivalence(saved_plan, plan):
            raise SystemExit(
                f"[train] resume refused: checkpoint plan "
                f"(rows {list(saved_plan.rows_per_rank)}, global "
                f"{saved_plan.global_rows}) and the current plan "
                f"(rows {plan.rows_per_rank.tolist()}, global "
                f"{plan.global_rows}) consume different global record "
                f"streams")
        saved_pipe = (meta.get("format") or {}).get("pipeline")
        cur_pipe = fmt.get("pipeline")
        if saved_pipe != cur_pipe:
            def _pdesc(rec):
                if not rec:
                    return "none"
                return (f"stages={len(rec['plan']['rows_per_rank'])} "
                        f"layers={rec['plan']['rows_per_rank']}")
            # params are stored per-leaf, so the restore itself is
            # bit-exact under any stage plan — log, never adapt
            print(f"[train] restore: pipeline stage plan changed: "
                  f"{_pdesc(saved_pipe)} -> {_pdesc(cur_pipe)}")
        specs = steps_mod.state_specs(model, tcfg, mesh)
        with jax.set_mesh(mesh):
            state = jax.device_put(host, named(mesh, specs))
        stream = meta.get("stream") or {}
        position = (int(meta["step"]),
                    int(stream.get("epoch", meta.get("epoch", 0))),
                    int(stream.get("batch_in_epoch", 0)))
        return state, position

    step_fn, sampler, loader, bspecs, fmt = build_runtime(mesh, plan)
    n_dp = dp_size(mesh)
    start_step = 0
    epoch = 0
    batch_in_epoch = 0
    if args.resume and mgr.latest_step() is not None:
        state, (start_step, epoch, batch_in_epoch) = restore_state(mesh,
                                                                   plan)
        print(f"[train] resumed from step {start_step} "
              f"(epoch {epoch}, batch {batch_in_epoch})")
    else:
        with jax.set_mesh(mesh):
            state = steps_mod.init_train_state(
                model, tcfg, mesh, jax.random.PRNGKey(tcfg.seed))

    monitor = StragglerMonitor(num_ranks=n_dp,
                               ema_decay=tcfg.het.straggler_ema,
                               replan_interval=tcfg.het.replan_interval)

    def save_meta():
        return {"epoch": epoch, "seed": tcfg.seed, "plan": plan,
                "format": fmt,
                "stream": {"epoch": epoch,
                           "batch_in_epoch": batch_in_epoch}}

    step = start_step
    losses = []
    step_s = []
    batch_rows_by_device: Dict[int, int] = {}
    t_start = time.perf_counter()
    body_raised = False
    try:
        while step < args.steps:
            try:
                with jax.set_mesh(mesh):
                    stream = _batches(loader, epoch, batch_in_epoch)
                    while step < args.steps:
                        with obs.span("train.iteration"):
                            with obs.span("train.input"):
                                b_epoch, b_index, raw = next(stream)
                                # hetsampler pads the *labels*: inputs
                                # are the shifted view
                                batch = jax.device_put({
                                    name: jnp.asarray(
                                        raw[name][:, :args.seq_len])
                                    for name in ("inputs", "labels",
                                                 "weights")}, bspecs)
                            if not batch_rows_by_device:
                                batch_rows_by_device = {
                                    s.device.id: int(s.data.shape[0])
                                    for s in batch["inputs"]
                                    .addressable_shards}
                            # rows: the real rows each rank takes
                            # this step, as the plan in force deals them
                            with obs.span(
                                    "train.step", step=step + 1,
                                    ranks=n_dp,
                                    rows=plan.rows_per_rank.tolist()
                                    ) as timed:
                                state, metrics = jax.block_until_ready(
                                    step_fn(state, batch))
                                # set when the step was compiled
                                timed.attrs["exchange_bytes"] = \
                                    obs.counters().get(
                                        "train.exchange_bytes", 0)
                            dt = timed.seconds
                            step += 1
                            epoch, batch_in_epoch = b_epoch, b_index
                            with obs.span("train.monitor"):
                                loss = float(metrics["loss"])
                                losses.append(loss)
                                step_s.append(dt)
                                plan = _monitor(monitor, engine, plan,
                                                sampler, step, dt)
                                if step % args.log_every == 0:
                                    print(f"[train] step {step:5d} loss "
                                          f"{loss:.4f} ({dt * 1e3:.0f} "
                                          f"ms)")
                                if tcfg.ckpt_every and \
                                        step % tcfg.ckpt_every == 0:
                                    with obs.span("train.checkpoint"):
                                        mgr.save(step,
                                                 jax.device_get(state),
                                                 meta=save_meta())
            except RemeshRequired as e:
                mgr.wait()                 # flush any in-flight write
                if mgr.latest_step() is None:
                    raise SystemExit(
                        f"[train] remesh required ({e}) but no "
                        f"checkpoint exists to restart from — set "
                        f"--ckpt-every") from e
                dead = set(monitor.dead_ranks().tolist())
                dpp = topo.data_per_pod
                alive = [p for p in range(topo.pods)
                         if not all(r in dead
                                    for r in range(p * dpp,
                                                   (p + 1) * dpp))]
                caps = tcfg.het.capacities
                caps_per_pod = (
                    [float(np.mean(caps[p * dpp:(p + 1) * dpp]))
                     for p in range(topo.pods)] if caps else None)
                decision = elastic.plan_remesh(
                    topo, alive, plan.global_rows, caps_per_pod,
                    round_buffer_to=max(tcfg.het.accum_steps, 1))
                print(f"[train] remesh: {decision.reason}")
                if not decision.restart_required:
                    # every pod still has live ranks, yet soft
                    # replanning just FAILED (that is what raised
                    # RemeshRequired) — re-planning from static
                    # capacities would assign real rows to the dead
                    # ranks and loop forever. Re-mesh granularity is
                    # whole pods; escalate loudly.
                    raise SystemExit(
                        f"[train] ranks {sorted(dead)} are dead but no "
                        f"whole pod is lost, and soft replanning cannot "
                        f"absorb them ({e}); shrink the global batch or "
                        f"drain the affected pod") from e
                if not elastic.validate_resume_equivalence(plan,
                                                           decision.plan):
                    raise SystemExit(
                        "[train] remesh produced a plan that consumes "
                        "a different global record stream") from e
                topo = decision.topology
                mesh = mesh_for_topology(topo)
                plan = decision.plan
                n_dp = dp_size(mesh)
                # capacities were indexed by the OLD rank numbering —
                # after the re-mesh the survivors are renumbered, so
                # the stale list would skew any later replan; the plan
                # from plan_remesh is authoritative now. accum_steps
                # scales to preserve the per-microbatch grid across
                # the DP-width change: the resumed trajectory stays
                # bit-identical (see elastic.RemeshDecision.accum_scale).
                tcfg = dataclasses.replace(
                    tcfg, het=dataclasses.replace(
                        tcfg.het, capacities=(),
                        accum_steps=(tcfg.het.accum_steps *
                                     decision.accum_scale)))
                if decision.accum_scale > 1:
                    print(f"[train] accum_steps scaled x"
                          f"{decision.accum_scale} to preserve the "
                          f"microbatch grid")
                step_fn, sampler, loader, bspecs, fmt = build_runtime(
                    mesh, plan)
                state, (step, epoch, batch_in_epoch) = restore_state(
                    mesh, plan)
                # the rollback discards the post-checkpoint trajectory:
                # drop its loss entries so the final summary reports
                # only steps that are part of the resumed run
                del losses[max(step - start_step, 0):]
                del step_s[max(step - start_step, 0):]
                monitor = StragglerMonitor(
                    num_ranks=n_dp, ema_decay=tcfg.het.straggler_ema,
                    replan_interval=tcfg.het.replan_interval)
                # remap surviving ranks; faults on the dead pod vanish
                # with it (mgr keeps its original ckpt fault hook so
                # transient-attempt counters survive the re-mesh)
                engine = engine.after_remesh(alive)
                print(f"[train] re-meshed to "
                      f"{dict(zip(mesh.axis_names, mesh.devices.shape))}"
                      f", resumed step {step} (epoch {epoch}, batch "
                      f"{batch_in_epoch})")
        mgr.save(step, jax.device_get(state), meta=save_meta(),
                 block=True)
    except BaseException:
        body_raised = True
        raise
    finally:
        # join the async writer on EVERY exit path (clean, SystemExit
        # from a failed remesh, any step error): the daemon thread
        # would otherwise die with the process and silently lose the
        # run's final checkpoint. On a clean exit a deferred write
        # error must PROPAGATE (the final checkpoint did not land);
        # while another exception is already unwinding, don't mask it
        # — print and let the original continue. (sys.exc_info() can't
        # make this call here: inside the except handler it reports
        # the wait error itself, so the flag is set by the body.)
        try:
            mgr.wait()
        except BaseException as werr:
            if not body_raised:
                raise
            print(f"[train] WARNING: checkpoint writer failed during "
                  f"shutdown: {werr!r}")
    wall = time.perf_counter() - t_start
    if not losses:                       # resumed an already-done run
        print(f"[train] nothing to do: checkpoint already at step "
              f"{step} >= --steps {args.steps}")
        return {"steps": step, "wall_s": wall}
    print(f"[train] done: {step - start_step} steps in {wall:.1f}s, "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    return {"steps": step, "wall_s": wall, "first_loss": losses[0],
            "last_loss": losses[-1], "losses": losses, "step_s": step_s,
            "batch_rows_by_device": batch_rows_by_device}


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--devices", default="1,1",
                    help="mesh shape: data,model or pod,data,model")
    ap.add_argument("--capacities", default="",
                    help="per-DP-rank relative capacities, e.g. 2,1,1,0")
    ap.add_argument("--weighting", default="tokens",
                    choices=list(cfgbase.WEIGHTING_MODES),
                    help="'canonical': order-canonical executor — "
                         "per-row grads summed in global-row order, "
                         "bit-identical across capacity replans (needs "
                         "plain allreduce, no overlap/compression)")
    ap.add_argument("--grad-reduction", default="allreduce",
                    choices=list(cfgbase.GRAD_REDUCTION_MODES))
    ap.add_argument("--compression", default="none",
                    choices=list(cfgbase.COMPRESSION_MODES))
    ap.add_argument("--bucket-mb", type=float, default=0.0,
                    help="bucketed flat-buffer reduction: bucket payload"
                         " in MiB of f32 (0 = legacy per-leaf walk)")
    ap.add_argument("--overlap", default="none",
                    choices=list(cfgbase.OVERLAP_MODES),
                    help="'buckets': double-buffered per-bucket exchange"
                         " fused with per-bucket optimizer updates,"
                         " after the backward pass; 'backward': flush"
                         " buckets DURING backprop as each layer's"
                         " grads land (also needs --no-scan-layers)."
                         " Both need an explicit --grad-reduction and"
                         " --bucket-mb > 0")
    ap.add_argument("--no-scan-layers", action="store_true",
                    help="unroll the layer stack instead of lax.scan "
                         "(required by --overlap backward and "
                         "--pipeline-stages > 1; larger HLO)")
    ap.add_argument("--pipeline-stages", type=int, default=1,
                    help="split the layer stack into N contiguous "
                         "pipeline stages sized by per-pod capacity "
                         "(core/pipeline.py); needs --no-scan-layers, "
                         "--overlap none and --accum >= N (the "
                         "accumulation microbatches are the 1F1B "
                         "stream). 1 = no pipelining")
    ap.add_argument("--pipeline-schedule", default="1f1b",
                    choices=list(cfgbase.PIPELINE_MODES),
                    help="microbatch schedule for --pipeline-stages > 1:"
                         " 1f1b (warmup / steady / drain, bounded "
                         "activation memory) or gpipe (all forwards "
                         "then all backwards)")
    ap.add_argument("--dry-run", action="store_true",
                    help="build mesh/plan, validate the config, print "
                         "the summary, and exit without training")
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "lamb"],
                    help="lamb = the paper's stated future work "
                         "(You et al. 2019) for large het batches")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--schedule", default="inverse_sqrt")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prefetch", type=int, default=2)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--replan-interval", type=int, default=100,
                    help="steps between straggler capacity replans")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="/tmp/hetseq_ckpt")
    ap.add_argument("--data-dir", default="/tmp/hetseq_data")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--chaos", default="",
                    help="fault injection: a schedule.json path or a "
                         "preset name "
                         f"({', '.join(sorted(chaos.PRESETS))}) — "
                         "deterministic per-rank slowdowns, rank/pod "
                         "kills, flaky reports, checkpoint-IO faults "
                         "(core/chaos.py)")
    ap.add_argument("--kill-pod", default="",
                    help="fault injection 'P@S': pod P stops reporting "
                         "from global step S (exercises the elastic "
                         "remesh restart); alias for a one-entry "
                         "--chaos kill schedule")
    return ap.parse_args(argv)


def main():
    use_compile_cache()
    train(parse_args())


if __name__ == "__main__":
    main()
