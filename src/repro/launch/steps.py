"""Step builders: jitted train / prefill / decode with explicit shardings.

``build_train_step`` assembles the full HetSeq step:
  1. weighted objective over the packed (dummy-padded) global batch —
     per-token weights make heterogeneous capacity exact (core M1/M3);
  2. optional gradient accumulation scan (core M4, shared scan core in
     core/accumulate.py);
  3. gradient reduction, selected by ``HetConfig.grad_reduction`` and
     ``HetConfig.bucket_mb``:
       * "allreduce"    — paper-faithful: XLA's automatic reduction from
         the shardings (FSDP => reduce-scatter + all-gather);
       * "bucketed_allreduce" — explicit flat-buffer reduction: grads
         are packed into fixed-size f32 buckets (core/buckets.py) and
         reduced with ONE psum_scatter + ONE all_gather over the whole
         DP axis set, instead of XLA's per-leaf collectives;
       * "hierarchical" — beyond-paper: params replicated over "pod",
         FSDP over "data"; in-pod reduction stays automatic (ICI), the
         cross-pod leg is an explicit shard_map(axis_names={"pod"})
         collective, optionally int8-compressed with error feedback.
         With ``bucket_mb > 0`` the cross-pod leg runs the bucketed
         engine: two collectives per step total, error feedback held
         in ONE flat (pods, num_buckets, bucket_elems) array; with
         ``bucket_mb == 0`` the legacy per-leaf walk (one quantize +
         one gather per leaf) is kept for comparison;
  4. AdamW update (optimizer state sharded like params = ZeRO-1).

``HetConfig.overlap="buckets"`` (both explicit reduction modes)
replaces steps 3+4 with the fused double-buffered pipeline: the
per-bucket exchange (core/buckets.py::exchange_buckets_overlapped)
overlaps bucket k+1's quantize/pack with bucket k's in-flight
collective, and the flat-view optimizer update
(optim/adam.py::apply_update_flat) for bucket k is applied the moment
its reduced payload lands — the optimizer moments then live packed as
one (num_buckets, bucket_elems) array in TrainState, replicated over
the reduction axes. In the backward-overlap flush pipeline LAMB
streams too: its moment updates and per-leaf norm partials land per
bucket, with only the trust-ratio application deferred to one trailing
elementwise pass (optim/lamb.py; the after-backward bucket engine
keeps LAMB's whole-stack barrier — see the rationale there).
Global-norm clipping keeps the pipelined exchange but updates behind a
barrier (the clip factor needs every bucket before the first moment
update).

``HetConfig.pipeline_stages > 1`` adds the pipe dimension: the uniform
layer stack is cut into contiguous capacity-sized stages
(core/pipeline.py StagePlan) and the accumulation microbatches stream
through them in 1F1B program order — per-stage VJP segments exchanged
through send/recv regions, grads reduced per-stage through the bucket
engine when ``grad_reduction="bucketed_allreduce"``
(_build_pipeline_step).

``input_specs`` provides ShapeDtypeStruct stand-ins for every cell of
the (architecture x shape) grid — the dry-run lowers against these, no
allocation ever happens.
"""
from __future__ import annotations

import functools
import logging
import math
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.configs.base import (ModelConfig, OptimizerConfig, ShapeConfig,
                                TrainConfig)
from repro.core import accumulate as acc
from repro.core import buckets as bkt
from repro.core import pipeline as pipe
from repro.core import weighting
from repro.launch import sharding as shr
from repro.launch.mesh import dp_axes as mesh_dp_axes, dp_size, tp_axis
from repro.models.blocks import ParallelCtx
from repro.models.model import Model
from repro.optim import adam, lamb, schedules
from repro.roofline.hlo import exchange_bytes, pool_move_bytes

logger = logging.getLogger(__name__)

# quantization block size for the compressed cross-pod exchanges
_BLOCK = 256


def make_parallel_ctx(mesh: Optional[Mesh]) -> ParallelCtx:
    if mesh is None:
        return ParallelCtx()
    return ParallelCtx(mesh=mesh, dp_axes=mesh_dp_axes(mesh),
                       tp_axis=tp_axis(mesh))


# --------------------------------------------------------------------------
# train state
# --------------------------------------------------------------------------


class TrainState(NamedTuple):
    params: Any
    opt: adam.AdamState
    err: Any                       # error-feedback state or () when unused
    # bucketed reduction: ONE flat (pods, num_buckets, bucket_elems) f32
    # array; legacy per-leaf reduction: a (pods, *leaf) pytree mirror
    # overlap="buckets": opt.m / opt.v are packed
    # (num_buckets, bucket_elems) arrays (core/buckets.py layout),
    # replicated over the reduction axes, NOT pytree mirrors


def _err_enabled(tcfg: TrainConfig, mesh: Mesh) -> bool:
    return (tcfg.het.grad_reduction == "hierarchical"
            and tcfg.het.compression != "none"
            and tcfg.het.error_feedback
            and "pod" in mesh.axis_names)


def _overlap_enabled(tcfg: TrainConfig, mesh: Mesh) -> bool:
    """Whether this config runs a fused per-bucket pipeline
    (``overlap`` in {"buckets", "backward"}).

    Overlap is a schedule of the bucketed engine, so it needs an
    explicit reduction mode with a bucket layout to pipeline over
    (``HetConfig.validate`` raises on misconfiguration); a mesh with
    no reduction axes silently falls back to the non-overlap path.
    """
    tcfg.het.validate()
    if tcfg.het.overlap == "none":
        return False
    if not _reduce_axes(tcfg, mesh):
        return False               # no reduction axes on this mesh
    return True


def validate_train_config(model: Model, tcfg: TrainConfig,
                          mesh: Mesh) -> None:
    """Full config validation at ``build_train_step`` time.

    Mesh-independent rules live in ``HetConfig.validate``; this adds
    the mesh/model-dependent rules so misconfigurations raise one
    clear ``ValueError`` up front instead of failing deep in the
    pipeline. Also used by ``launch/train.py --dry-run``.
    """
    from repro.models import transformer as tr

    het = tcfg.het.validate()
    if not 0.0 <= tcfg.label_smoothing < 1.0:
        raise ValueError(
            f"TrainConfig.label_smoothing must be in [0, 1), got "
            f"{tcfg.label_smoothing}")
    if het.grad_reduction == "bucketed_allreduce" \
            and not mesh_dp_axes(mesh):
        raise ValueError(
            "grad_reduction='bucketed_allreduce' needs a mesh with "
            f"data-parallel axes; got {mesh.axis_names}")
    if het.overlap == "backward":
        # model rules checked UNCONDITIONALLY: a mesh with no reduction
        # axes falls back to the non-overlap schedule, but an
        # unsupported stack plan used to ride that fallback silently and
        # then blow up the moment the same config met a real mesh —
        # supports_staged_backward drives a loud build-time error either
        # way (tests/test_overlap.py regression)
        if not tr.supports_staged_backward(model.cfg):
            raise ValueError(
                "HetConfig.overlap='backward' stages the backward over "
                "the uniform block stack (dense | moe | mla); stack "
                f"plan '{tr.stack_plan(model.cfg)}' of "
                f"'{model.cfg.name}' is not supported — use "
                "overlap='buckets'")
        if model.cfg.scan_layers:
            raise ValueError(
                "HetConfig.overlap='backward' needs ModelConfig."
                "scan_layers=False: the staged layer-by-layer backward "
                "is an unrolled program, and bit-exactness with the "
                "monolithic path requires the monolithic stack "
                "unrolled too (launch/train.py: --no-scan-layers)")
    if het.pipeline_stages > 1:
        if not tr.supports_staged_backward(model.cfg):
            raise ValueError(
                "HetConfig.pipeline_stages > 1 cuts the uniform block "
                "stack (dense | moe | mla) into contiguous stages; "
                f"stack plan '{tr.stack_plan(model.cfg)}' of "
                f"'{model.cfg.name}' is not supported")
        if model.cfg.scan_layers:
            raise ValueError(
                "HetConfig.pipeline_stages > 1 needs ModelConfig."
                "scan_layers=False: the per-stage VJP segments are an "
                "unrolled program, and bit-exactness with pure DP "
                "requires the monolithic stack unrolled too "
                "(launch/train.py: --no-scan-layers)")
        if model.cfg.num_layers < het.pipeline_stages:
            raise ValueError(
                f"pipeline_stages={het.pipeline_stages} exceeds the "
                f"{model.cfg.num_layers}-layer stack of "
                f"'{model.cfg.name}' (every stage needs >= 1 layer)")
        if "pipe" in mesh.axis_names \
                and mesh.shape["pipe"] != het.pipeline_stages:
            raise ValueError(
                f"mesh 'pipe' axis has size {mesh.shape['pipe']} but "
                f"HetConfig.pipeline_stages={het.pipeline_stages} — "
                "build the mesh with pipe=pipeline_stages "
                "(launch/mesh.py)")


def _flat_barrier_update(pb, red, m, v, lr_step, ocfg, lr, *, inv_w,
                         dmask, segs, n_leaves):
    """Whole-stack flat optimizer update behind the barrier.

    Shared by the after-backward ("buckets") and backward-overlap
    pipelines for configs whose statistics need every reduced bucket
    BEFORE the first moment update (global-norm clipping), and by the
    after-backward engine for ALL of LAMB (the backward-overlap flush
    pipeline streams LAMB instead — optim/lamb.py has the full
    exactness rationale). Returns
    (new_pb, new_m, new_v, gnorm, mean trust ratio).
    """
    gsc = red * inv_w
    gnorm = jnp.sqrt(jnp.sum(gsc * gsc))
    cs = (jnp.minimum(1.0, ocfg.grad_clip /
                      jnp.maximum(gnorm, 1e-9))
          if ocfg.grad_clip > 0 else None)
    if ocfg.name == "lamb":
        new_pb, new_m, new_v, trust = lamb.apply_update_flat(
            pb, gsc, m, v, lr_step, ocfg, lr,
            decay_mask=dmask, seg_ids=segs,
            num_leaves=n_leaves, clip_scale=cs)
    else:
        new_pb, new_m, new_v = adam.apply_update_flat(
            pb, gsc, m, v, lr_step, ocfg, lr,
            decay_mask=dmask, clip_scale=cs)
        trust = jnp.ones((), jnp.float32)
    return new_pb, new_m, new_v, gnorm, trust


def _reduce_axes(tcfg: TrainConfig, mesh: Mesh) -> Tuple[str, ...]:
    """The mesh axes the explicit bucketed reduction runs over."""
    if tcfg.het.grad_reduction == "bucketed_allreduce":
        return mesh_dp_axes(mesh)
    return ("pod",) if "pod" in mesh.axis_names else ()


def stage_plan_for(model: Model,
                   tcfg: TrainConfig) -> Optional[pipe.StagePlan]:
    """The pipeline StagePlan for this config cell (None when off).

    When ``HetConfig.capacities`` has exactly ``pipeline_stages``
    positive entries they double as the per-stage speed scores — the
    same weight table the DP batch planner uses sizes the layer cut
    (core/pipeline.py). Anything else (empty / per-DP-rank-shaped /
    containing zeros, which mark dead DP ranks but cannot mark a
    pipeline stage) gets the uniform cut.
    """
    S = tcfg.het.pipeline_stages
    if S <= 1:
        return None
    caps = tcfg.het.capacities
    if len(caps) == S and all(c > 0 for c in caps):
        return pipe.plan_stages(model.cfg.num_layers, caps)
    return pipe.uniform_stages(model.cfg.num_layers, S)


def bucket_layout(model: Model, tcfg: TrainConfig,
                  mesh: Mesh) -> Optional[bkt.BucketLayout]:
    """The gradient bucket grid for this (model, config, mesh) cell.

    The bucket size is rounded so every bucket divides into per-rank
    shards of whole quantization blocks (ranks * _BLOCK).
    """
    if tcfg.het.bucket_mb <= 0:
        return None
    axes = _reduce_axes(tcfg, mesh)
    if not axes:
        return None
    ranks = 1
    for a in axes:
        ranks *= mesh.shape[a]
    params_shape = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    return bkt.build_layout(params_shape, bucket_mb=tcfg.het.bucket_mb,
                            multiple_of=ranks * _BLOCK)


def checkpoint_format(model: Model, tcfg: TrainConfig, mesh: Mesh) -> Dict:
    """The checkpoint ``"format"`` meta block for this config cell.

    Records how this cell lays TrainState out on disk: which fields are
    saved packed (``overlap="buckets"`` stores the optimizer moments as
    one (num_buckets, bucket_elems) stack) and the versioned
    ``BucketLayout`` record + fingerprint describing that grid, so a
    restore into ANY other cell can translate through the flat stream
    (checkpoint/repack.py) instead of failing on shape mismatch.
    ``hosts`` is the v3 per-host shard count (one writer per pod — the
    fleet unit that owns its own disk); the layout record carries the
    matching bucket-row extents each host writes.
    """
    from repro.checkpoint import repack

    hosts = int(mesh.shape["pod"]) if "pod" in mesh.axis_names else 1
    fmt: Dict[str, Any] = {"version": repack.FORMAT_VERSION,
                           "state": "pytree", "packed_fields": [],
                           "layout": None,
                           "hosts": hosts,
                           # which HetConfig.overlap mode wrote this
                           # checkpoint — restore logs (never silently
                           # adapts) when the restore target differs
                           "overlap": tcfg.het.overlap,
                           # stage partition that wrote this checkpoint
                           # (core/pipeline.py stage_record, or None
                           # without pipelining). Params are stored
                           # per-leaf, so a checkpoint restores
                           # bit-exactly under ANY stage plan — the
                           # record exists so restore can LOG the plan
                           # change, and repack.py can validate it
                           "pipeline": None}
    splan = stage_plan_for(model, tcfg)
    if splan is not None:
        fmt["pipeline"] = pipe.stage_record(splan)
    if _overlap_enabled(tcfg, mesh):
        lo = bucket_layout(model, tcfg, mesh)
        params_shape = jax.eval_shape(model.init_params,
                                      jax.random.PRNGKey(0))
        paths = [repack.path_key(p) for p, _ in
                 jax.tree_util.tree_flatten_with_path(params_shape)[0]]
        rec = bkt.layout_record(lo, leaf_paths=paths, hosts=hosts)
        fmt.update(state="packed",
                   packed_fields=["opt/m", "opt/v"],
                   layout=rec,
                   fingerprint=rec["fingerprint"])
    return fmt


def state_shapes(model: Model, tcfg: TrainConfig, mesh: Mesh):
    params_shape = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    if _overlap_enabled(tcfg, mesh):
        # fused per-bucket pipeline: moments live packed in the flat
        # bucket layout (NOTE: layout depends on the mesh's reduction
        # ranks — re-meshing an overlap checkpoint needs a repack)
        lo = bucket_layout(model, tcfg, mesh)
        opt_shape = jax.eval_shape(functools.partial(
            adam.init_state_flat, lo.num_buckets, lo.bucket_elems,
            tcfg.optimizer))
    else:
        opt_shape = jax.eval_shape(
            functools.partial(adam.init_state, cfg=tcfg.optimizer),
            params_shape)
    if _err_enabled(tcfg, mesh):
        pods = mesh.shape["pod"]
        layout = bucket_layout(model, tcfg, mesh)
        if layout is not None:
            err_shape: Any = jax.ShapeDtypeStruct(
                layout.error_shape(pods), jnp.float32)
        else:
            err_shape = jax.tree.map(
                lambda p: jax.ShapeDtypeStruct((pods,) + p.shape,
                                               jnp.float32),
                params_shape)
    else:
        err_shape = ()
    return TrainState(params=params_shape, opt=opt_shape, err=err_shape)


def _strip_axes(spec: P, drop: Tuple[str, ...]) -> P:
    """Remove the given mesh axes from a PartitionSpec (replicate)."""
    out = []
    for ax in spec:
        if isinstance(ax, tuple):
            kept = tuple(a for a in ax if a not in drop)
            out.append(kept if kept else None)
        else:
            out.append(None if ax in drop else ax)
    return P(*out)


def state_specs(model: Model, tcfg: TrainConfig, mesh: Mesh) -> TrainState:
    shapes = state_shapes(model, tcfg, mesh)
    hier = (tcfg.het.grad_reduction == "hierarchical"
            and "pod" in mesh.axis_names)
    bucketed_ar = tcfg.het.grad_reduction == "bucketed_allreduce"
    pspecs = shr.param_specs(model.cfg, shapes.params, mesh)
    if hier or bucketed_ar:
        # explicit-reduction modes: params replicated across the manual
        # reduction axes so the gradient leg is ours to schedule
        # (hierarchical keeps FSDP over "data"; bucketed_allreduce
        # replicates over the whole DP set)
        drop = ("pod",) if hier else _reduce_axes(tcfg, mesh)
        pspecs = jax.tree.map(lambda s: _strip_axes(s, drop), pspecs,
                              is_leaf=lambda x: isinstance(x, P))
        # token-embedding gathers with a sharded vocab dim hit an XLA
        # SPMD-partitioner bug inside partially-manual regions; shard the
        # table on d_model only (gather pass-through dim) in this mode
        if isinstance(pspecs, dict) and "embed" in pspecs:
            tp = "model" if "model" in mesh.axis_names else None
            vshape = shapes.params["embed"].shape
            pspecs = dict(pspecs)
            pspecs["embed"] = shr.fit_spec(vshape, P(None, tp), mesh)
    if _overlap_enabled(tcfg, mesh):
        # packed moments: replicated over the reduction axes (the flat
        # stack mixes every leaf's sharding — the ZeRO-1 mirror does
        # not apply; documented trade in ROADMAP.md)
        ospecs = adam.AdamState(step=P(), m=P(), v=P())
    else:
        ospecs = adam.AdamState(step=P(), m=pspecs, v=pspecs)
    if shapes.err == ():
        especs: Any = ()
    elif isinstance(shapes.err, jax.ShapeDtypeStruct):
        especs = P("pod")              # flat bucketed error state
    else:
        especs = jax.tree.map(lambda s: P("pod", *s), pspecs,
                              is_leaf=lambda x: isinstance(x, P))
    return TrainState(params=pspecs, opt=ospecs, err=especs)


def init_train_state(model: Model, tcfg: TrainConfig, mesh: Mesh,
                     key) -> TrainState:
    """Initialize on-device with the right shardings (M8: same init
    everywhere — a single global RNG key IS the broadcast)."""
    specs = state_specs(model, tcfg, mesh)
    shapes = state_shapes(model, tcfg, mesh)

    def init(k):
        params = model.init_params(k)
        if _overlap_enabled(tcfg, mesh):
            lo = bucket_layout(model, tcfg, mesh)
            opt = adam.init_state_flat(lo.num_buckets, lo.bucket_elems,
                                       tcfg.optimizer)
        else:
            opt = adam.init_state(params, tcfg.optimizer)
        if shapes.err == ():
            err: Any = ()
        else:
            err = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), shapes.err)
        return TrainState(params=params, opt=opt, err=err)

    with jax.set_mesh(mesh):
        return jax.jit(init, out_shardings=shr.named(mesh, specs))(key)


def init_params_sharded(model: Model, mesh: Mesh, key):
    """Initialize bare params with the production shardings (serving)."""
    params_shape = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    pspecs = shr.param_specs(model.cfg, params_shape, mesh)
    with jax.set_mesh(mesh):
        return jax.jit(model.init_params,
                       out_shardings=shr.named(mesh, pspecs))(key)


def init_cache_sharded(model: Model, shape: ShapeConfig, mesh: Mesh):
    """Zero cache with the decode-step shardings."""
    b = shape.global_batch
    cache_shape = jax.eval_shape(
        functools.partial(model.init_cache, b, shape.seq_len))
    cspecs = shr.cache_specs(model.cfg, cache_shape, mesh, b)
    with jax.set_mesh(mesh):
        return jax.jit(functools.partial(model.init_cache, b,
                                         shape.seq_len),
                       out_shardings=shr.named(mesh, cspecs))()


# --------------------------------------------------------------------------
# gradient reduction modes
# --------------------------------------------------------------------------


def _quant_lastdim(x: jnp.ndarray, block: int):
    """Blockwise int8 quantization along the LAST dim only.

    Unlike the flatten-everything kernel wrapper, this preserves the
    sharding of every other dim — flattening a (data, model)-sharded
    matrix forces XLA to all-gather it before the reshape (measured:
    38 GB of replicated gradient copies in the hier step).
    """
    last = x.shape[-1]
    bs = min(block, last)
    x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, (-last) % bs)])
    nb = x.shape[-1] // bs
    blocks = x.reshape(*x.shape[:-1], nb, bs)
    scale = jnp.max(jnp.abs(blocks), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(blocks / scale), -127, 127).astype(jnp.int8)
    return q, scale[..., 0], last


def _dequant_lastdim(q: jnp.ndarray, scale: jnp.ndarray, last: int):
    deq = q.astype(jnp.float32) * scale[..., None]
    deq = deq.reshape(*deq.shape[:-2], -1)
    return deq[..., :last]


def _cross_pod_reduce(grads: Any, err: Any, compress: str, pods: int,
                      block_size: int = _BLOCK) -> Tuple[Any, Any]:
    """LEGACY per-leaf walk, inside shard_map(manual={"pod"}).

    One collective per pytree leaf (compressed: one quantize + one
    full-payload gather per leaf — O(pods) receive bandwidth). Kept as
    the comparison baseline for the bucketed engine and for
    ``bucket_mb == 0`` configs; benchmarks/reduce_bench.py measures the
    difference.

    grads: this pod's gradient contribution (auto-sharded over data).
    err:   (1, *shape) this pod's persistent error-feedback state.
    """
    def leaf(g, e):
        if compress == "none":
            return jax.lax.psum(g, "pod"), e
        gf = g.astype(jnp.float32)
        if gf.ndim == 1:
            gf = gf[None]
            squeeze = True
        else:
            squeeze = False
        corrected = gf + (e.reshape(gf.shape).astype(jnp.float32)
                          if e is not None else 0.0)
        q, s, last = _quant_lastdim(corrected, block_size)
        deq_local = _dequant_lastdim(q, s, last)
        new_e = ((corrected - deq_local).reshape(e.shape)
                 if e is not None else e)
        # int8 payload + per-block scales are what cross the DCN link;
        # gathered along a NEW leading pod axis (all shardings preserved)
        q_all = jax.lax.all_gather(q, "pod")
        s_all = jax.lax.all_gather(s, "pod")
        deq = jnp.sum(q_all.astype(jnp.float32) * s_all[..., None],
                      axis=0)
        out = deq.reshape(*deq.shape[:-2], -1)[..., :last]
        if squeeze:
            out = out[0]
        return out.astype(g.dtype), new_e

    if err == ():
        outs = jax.tree.map(lambda g: leaf(g, None)[0], grads)
        return outs, ()
    flat_g, treedef = jax.tree.flatten(grads)
    flat_e = treedef.flatten_up_to(err)
    pairs = [leaf(g, e) for g, e in zip(flat_g, flat_e)]
    return (treedef.unflatten([p[0] for p in pairs]),
            treedef.unflatten([p[1] for p in pairs]))


def _reduce_bucketed(
    grads: Any,
    err: Any,
    *,
    axis,
    axis_size: int,
    compress: str,
    layout: bkt.BucketLayout,
    impl: str = "reference",
    block_size: int = _BLOCK,
) -> Tuple[Any, Any]:
    """THE bucketed-reduction entry point, inside shard_map(manual).

    Shared by both explicit modes — ``axis="pod"`` for the cross-pod
    leg of "hierarchical", ``axis=<dp axes>`` for "bucketed_allreduce".
    Packs the whole gradient pytree into the fixed-size bucket stack,
    runs the monolithic two-collective exchange, and unpacks. ``err``
    is this rank's (1, num_buckets, bucket_elems) slice of the flat
    error state, or None when error feedback is off. The overlap mode
    does NOT go through here — its fused reduce+optimizer pipeline
    never materializes the unpacked gradient tree (see
    build_train_step's overlap branch).
    """
    flat = bkt.pack_buckets(grads, layout)
    e = (err.reshape(layout.num_buckets, layout.bucket_elems)
         if err is not None else None)
    red, new_e = bkt.exchange_buckets(
        flat, e, axis=axis, axis_size=axis_size,
        compress=(compress != "none"), block_size=block_size,
        impl=impl, total=layout.total)
    out = bkt.unpack_buckets(red, layout)
    if new_e is None:
        return out, None
    return out, new_e.reshape(1, layout.num_buckets, layout.bucket_elems)


# --------------------------------------------------------------------------
# backward-overlap step (HetConfig.overlap="backward")
# --------------------------------------------------------------------------


def _path_top(entry) -> str:
    """Top-level key of a tree_flatten_with_path path entry."""
    for attr in ("key", "name", "idx"):
        if hasattr(entry, attr):
            return str(getattr(entry, attr))
    return str(entry)


def _staged_leaf_pieces(params_shape: Any, cfg: ModelConfig):
    """Per-leaf ``(offset_within_leaf, n, backward_stage)`` pieces.

    The model's layer partition mapped onto the flat stream: stacked
    ``layers`` leaves split into per-layer slices landing back to
    front (layer *l* at stage ``L - l``), the head leaves at stage 0,
    the embedding table last (stage ``L + 1`` — a tied table also
    receives a head-stage contribution, so its grad is only final at
    the end). Feeds ``core/buckets.py::bucket_readiness``.
    """
    from repro.models import transformer as tr

    L = cfg.num_layers
    head_keys = set(tr.head_param_keys(cfg))
    pieces = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            params_shape)[0]:
        n = int(math.prod(leaf.shape)) if leaf.shape else 1
        top = _path_top(path[0])
        if top == "layers":
            if n % L:
                raise ValueError(
                    f"stacked leaf {jax.tree_util.keystr(path)} of "
                    f"{n} elements does not split into {L} layers")
            per = n // L
            pieces.append([(l * per, per, L - l) for l in range(L)])
        elif top == "embed":
            pieces.append([(0, n, L + 1)])
        elif top in head_keys:
            pieces.append([(0, n, 0)])
        else:
            raise ValueError(
                f"overlap='backward': unexpected param subtree "
                f"'{top}' (uniform stack expects embed / final_norm / "
                f"lm_head / layers)")
    return pieces


def _build_backward_overlap_step(model: Model, tcfg: TrainConfig,
                                 mesh: Mesh, *, layout: bkt.BucketLayout,
                                 hier: bool, compress: str,
                                 use_err: bool, fused_stream: bool):
    """The ``overlap="backward"`` train step: flush gradient buckets
    DURING backprop instead of after it.

    Structure: the batch is reshaped rank-major and every backward stage
    is a vmapped per-layer VJP in plain SPMD at the TOP level of the
    jitted program (models/transformer.py staged segments — requires
    ``scan_layers=False`` so the monolithic comparison path compiles
    the same unrolled dots), while each bucket's two-collective
    exchange runs in its own small shard_map(manual) region, issued
    the moment the bucket's last contributing stage lands
    (core/buckets.py::BucketFlushPipeline, readiness derived from the
    layer partition). The program-order interleaving of exchange
    regions with the remaining backward stages is what hands the
    runtime the overlap; the CPU host mesh executes collectives
    eagerly, so the modeled bwd+link timeline in
    benchmarks/overlap_bench.py is the claim — exactly as for
    ``overlap="buckets"``.

    Exactness: fp32 with ``grad_clip=0`` is bit-identical to the
    monolithic path (same config, ``overlap="none"``) — per-bucket
    exchanges match the monolithic exchange slice-for-slice and the
    flat AdamW stream matches the tree update (tests/test_overlap.py).
    LAMB streams its moment updates and norm partials per bucket with
    one trailing trust pass (optim/lamb.py — bitwise-equal to the
    barrier form by construction); global-norm clip keeps the
    in-backward pipelined exchange but applies the flat update behind
    a barrier. Gradient accumulation
    stages every microbatch's backward and flushes only during the
    last one (the bucket is final only then); the accumulator is the
    fp32 stream buffer, so bf16-carry configs differ from the
    monolithic bf16 carry by that last rounding step (documented
    trade).
    """
    from repro.models import transformer as tr

    cfg = model.cfg
    ocfg = tcfg.optimizer
    accum = max(1, tcfg.het.accum_steps)
    q_impl = tcfg.het.quantize_impl
    dp = mesh_dp_axes(mesh)
    n_dp = dp_size(mesh)
    n_pods = mesh.shape["pod"] if "pod" in mesh.axis_names else 1
    L = cfg.num_layers
    ranks = n_pods if hier else n_dp
    inner_dp = (n_dp // n_pods) if hier else 1
    red_axis: Any = "pod" if hier else (dp if len(dp) > 1 else dp[0])
    axis_set = {"pod"} if hier else set(dp)
    rank_spec = P("pod", "data") if hier else P(dp)
    buf_spec = P("pod") if hier else P(dp if len(dp) > 1 else dp[0])
    nb, be = layout.num_buckets, layout.bucket_elems
    shard = be // ranks
    compress_flag = compress != "none"
    dmask = bkt.decay_mask(layout)
    segs = bkt.segment_ids(layout) if ocfg.name == "lamb" else None
    n_leaves = len(layout.sizes)
    params_shape = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    readiness = bkt.bucket_readiness(
        layout, _staged_leaf_pieces(params_shape, cfg))
    token_frontend = cfg.frontend == "token"
    inner_ctx = ParallelCtx(mesh=mesh,
                            dp_axes=("data",) if hier else (),
                            tp_axis=tp_axis(mesh))
    seg = tr.staged_uniform_segments(
        cfg, inner_ctx, label_smoothing=tcfg.label_smoothing)
    embed_fn, layer_fn = seg["embed_fn"], seg["layer_fn"]
    head_fn, head_keys = seg["head_fn"], seg["head_keys"]

    # stream-offset bookkeeping per top-level subtree, flatten order
    subtree_slots: Dict[str, list] = {}
    for (path, _), off, size in zip(
            jax.tree_util.tree_flatten_with_path(params_shape)[0],
            layout.offsets, layout.sizes):
        subtree_slots.setdefault(_path_top(path[0]), []).append(
            (off, size))

    def scatter_subtree(buf, top, grads, layer=None):
        """Scatter-add a landed grad subtree into the stream buffer."""
        leaves = jax.tree.leaves(grads)
        # zero-leaf subtrees (non-parametric norms) never reach the
        # stream
        slots = subtree_slots.get(top, [])
        assert len(leaves) == len(slots), (top, len(leaves), len(slots))
        for g, (off, size) in zip(leaves, slots):
            if layer is not None:
                per = size // L
                off, size = off + layer * per, per
            buf = buf.at[:, off:off + size].add(
                g.reshape(ranks, size).astype(jnp.float32))
        return buf

    def staged_microbatch(params, lps, mb, buf, flush=None,
                          on_loss=None):
        """One microbatch's staged forward + layer-by-layer backward.

        Gradients accumulate into ``buf`` ((ranks, padded_total) f32
        stream rows, one per reduction rank) as each stage's cotangent
        lands; ``flush(stage, buf)`` fires after every landing (the
        LAST microbatch wires the bucket flush pipeline there);
        ``on_loss(o, w)`` fires once the forward objective exists —
        before any flush, so the fused update hook can close over the
        global weight sum. Returns (buf, o, w), o/w per-rank sums.
        """
        emb_p = {"embed": params["embed"]} if token_frontend else {}
        x = jax.vmap(embed_fn, in_axes=(None, 0))(emb_p, mb["inputs"])
        # x: (ranks, rows, S, d) for BOTH frontends — stub inputs are
        # already (rows, S, d), so seq_len must come from the
        # post-embed activation, not from inputs.shape[-1]
        positions = jnp.arange(x.shape[-2])
        xs = [x]
        auxs = []
        for l in range(L):
            x, a = jax.vmap(layer_fn, in_axes=(None, 0, None))(
                lps[l], x, positions)
            xs.append(x)
            auxs.append(a)
        hp = {k: params[k] for k in head_keys}

        def head_stage(hp_, x_l, lab, wt):
            (ce, w), vjp = jax.vjp(
                lambda q, xx: head_fn(q, xx, lab, wt), hp_, x_l)
            g_hp, x_cot = vjp((jnp.ones((), jnp.float32),
                               jnp.zeros((), jnp.float32)))
            return ce, w, g_hp, x_cot

        ce, w, g_hp, x_cot = jax.vmap(
            head_stage, in_axes=(None, 0, 0, 0))(
            hp, xs[L], mb["labels"], mb["weights"])
        aux_tot = jnp.zeros_like(ce)
        for a in auxs:
            aux_tot = aux_tot + a
        o = ce + aux_tot * jax.lax.stop_gradient(w)
        if on_loss is not None:
            on_loss(o, w)
        for key in head_keys:
            buf = scatter_subtree(buf, key, g_hp[key])
        if flush is not None:
            flush(0, buf)
        w_sg = jax.lax.stop_gradient(w)

        def layer_stage(lp, x_l, xc, ac):
            _, vjp = jax.vjp(
                lambda q, xx: layer_fn(q, xx, positions), lp, x_l)
            return vjp((xc, ac))

        for l in reversed(range(L)):
            g_lp, x_cot = jax.vmap(
                layer_stage, in_axes=(None, 0, 0, 0))(
                lps[l], xs[l], x_cot, w_sg)
            buf = scatter_subtree(buf, "layers", g_lp, layer=l)
            if flush is not None:
                flush(L - l, buf)
        if token_frontend:
            def embed_stage(ep, i, xc):
                _, vjp = jax.vjp(lambda q: embed_fn(q, i), ep)
                return vjp(xc)[0]

            g_emb = jax.vmap(embed_stage, in_axes=(None, 0, 0))(
                emb_p, mb["inputs"], x_cot)
            buf = scatter_subtree(buf, "embed", g_emb["embed"])
        if flush is not None:
            flush(L + 1, buf)
        return buf, o, w

    def split_rank_microbatches(sb):
        """Per-rank accumulation split, matching the monolithic
        acc.split_microbatches row assignment (inner-rank-major, so
        every microbatch takes an equal slice of every inner DP
        rank's buffer)."""
        if accum == 1:
            return [sb]

        def split(a):
            b = a.shape[1]
            if b % (inner_dp * accum):
                raise ValueError(
                    f"rows {b} per reduction rank not divisible by "
                    f"accum {accum} x inner ranks {inner_dp}")
            a2 = a.reshape(ranks, inner_dp, accum,
                           b // inner_dp // accum, *a.shape[2:])
            a2 = jnp.swapaxes(a2, 1, 2)
            return a2.reshape(ranks, accum, b // accum, *a.shape[2:])

        s = {k: split(v) for k, v in sb.items()}
        return [jax.tree.map(lambda a: a[:, i], s) for i in range(accum)]

    def step(state: TrainState, batch: Dict) -> Tuple[TrainState, Dict]:
        lr_step = state.opt.step + 1
        lr = schedules.learning_rate(ocfg, lr_step)
        params = state.params
        sb = jax.tree.map(
            lambda v: jax.lax.with_sharding_constraint(
                v.reshape(ranks, v.shape[0] // ranks, *v.shape[1:]),
                rank_spec), batch)
        mbs = split_rank_microbatches(sb)
        lps = [jax.tree.map(lambda a: a[l], params["layers"])
               for l in range(L)]
        pb = bkt.pack_buckets(params, layout)
        err_in = state.err if use_err else None   # (pods, nb, be)

        def prep(k, raw_k):
            """Send-side leg for bucket k: quantize/pack per rank at
            the top level (no collectives — it overlaps the previous
            bucket's in-flight exchange)."""
            x_k = raw_k.reshape(ranks, ranks, shard)
            if not compress_flag:
                return x_k, None
            e_k = (err_in[:, k].reshape(ranks, ranks, shard)
                   if use_err else None)
            pv = jax.vmap(
                lambda xk, ek: bkt.prepare_bucket(
                    xk, ek, compress=True, block_size=_BLOCK,
                    key=None, impl=q_impl, interpret=False),
                in_axes=(0, 0 if use_err else None))
            return pv(x_k, e_k)

        def exchange(k, prepared):
            """Link + receive legs for ONE bucket, in its own small
            manual region — the only collectives in the program, so
            they interleave with the staged backward in program
            order."""
            payload, resid1 = prepared
            if compress_flag and use_err:
                def region(pl, rs):
                    onehot = bkt.rank_onehot(red_axis, ranks)
                    red, ne = bkt.exchange_prepared_bucket(
                        pl[0], rs[0], axis=red_axis, axis_size=ranks,
                        compress=True, block_size=_BLOCK, impl=q_impl,
                        interpret=False, onehot=onehot)
                    return red, ne[None]

                return jax.shard_map(
                    region, mesh=mesh, in_specs=(buf_spec, buf_spec),
                    out_specs=(P(), buf_spec), axis_names=axis_set,
                    check_vma=False)(payload, resid1)

            def region(pl):
                onehot = bkt.rank_onehot(red_axis, ranks)
                red, _ = bkt.exchange_prepared_bucket(
                    pl[0], None, axis=red_axis, axis_size=ranks,
                    compress=compress_flag, block_size=_BLOCK,
                    impl=q_impl, interpret=False, onehot=onehot)
                return red

            red = jax.shard_map(
                region, mesh=mesh, in_specs=buf_spec, out_specs=P(),
                axis_names=axis_set, check_vma=False)(payload)
            return red, None

        cell: Dict[str, Any] = {}
        if fused_stream:
            if ocfg.name == "lamb":
                # stream moments + per-leaf norm partials per bucket;
                # the trust-scaled step itself trails (finish below)
                def hook(ssq, red_k, k):
                    g_k = red_k * cell["inv_w"]
                    pf, upd, mf, vf = adam.flat_adamw_terms(
                        pb[k], g_k, state.opt.m[k], state.opt.v[k],
                        lr_step, ocfg, decay_mask=dmask[k])
                    psq, usq = lamb.bucket_norm_terms(
                        pf, upd, segs[k], n_leaves)
                    return (ssq + jnp.sum(g_k * g_k),
                            (pf, upd, mf, vf, psq, usq))
            else:
                def hook(ssq, red_k, k):
                    g_k = red_k * cell["inv_w"]
                    out = adam.apply_update_flat(
                        pb[k], g_k, state.opt.m[k], state.opt.v[k],
                        lr_step, ocfg, lr, decay_mask=dmask[k])
                    return ssq + jnp.sum(g_k * g_k), out

            pipeline = bkt.BucketFlushPipeline(
                readiness, prep, exchange, bucket_fn=hook,
                fn_carry=jnp.zeros((), jnp.float32))
        else:
            pipeline = bkt.BucketFlushPipeline(readiness, prep,
                                               exchange)

        def flush(stage, buf):
            pipeline.flush_ready_buckets(
                stage, lambda k: buf[:, k * be:(k + 1) * be])

        buf = jax.lax.with_sharding_constraint(
            jnp.zeros((ranks, layout.padded_total), jnp.float32),
            buf_spec)
        o_acc = jnp.zeros((ranks,), jnp.float32)
        w_acc = jnp.zeros((ranks,), jnp.float32)
        for i, mb in enumerate(mbs):
            if i == accum - 1:
                def on_loss(o_mb, w_mb, _oa=o_acc, _wa=w_acc):
                    o_t, w_t = _oa + o_mb, _wa + w_mb
                    cell["o"], cell["w"] = o_t, w_t
                    w_glob = jnp.sum(w_t)
                    cell["w_glob"] = w_glob
                    cell["inv_w"] = 1.0 / jnp.maximum(w_glob, 1e-9)

                buf, o_mb, w_mb = staged_microbatch(
                    params, lps, mb, buf, flush=flush, on_loss=on_loss)
            else:
                buf, o_mb, w_mb = staged_microbatch(params, lps, mb,
                                                    buf)
                o_acc = o_acc + o_mb
                w_acc = w_acc + w_mb

        outs, errs, fc = pipeline.finish()
        o, w = jnp.sum(cell["o"]), cell["w_glob"]
        if fused_stream and ocfg.name == "lamb":
            # finish() hands outs back in BUCKET-INDEX order whatever
            # order the buckets flushed in — so the partial-norm
            # combination below is the canonical one apply_update_flat
            # uses, and the streamed step is bitwise the barrier step
            pf = jnp.stack([row[0] for row in outs])
            upd = jnp.stack([row[1] for row in outs])
            trust_v = lamb.trust_from_norms(
                lamb.combine_norm_terms([row[4] for row in outs]),
                lamb.combine_norm_terms([row[5] for row in outs]))
            new_pb = lamb.apply_trust(
                pf, upd, lr, segs, trust_v).astype(pb.dtype)
            new_m = jnp.stack(
                [row[2] for row in outs]).astype(state.opt.m.dtype)
            new_v = jnp.stack(
                [row[3] for row in outs]).astype(state.opt.v.dtype)
            gnorm = jnp.sqrt(fc)
            trust = jnp.mean(trust_v[:n_leaves])
        elif fused_stream:
            new_pb = jnp.stack([row[0] for row in outs])
            new_m = jnp.stack([row[1] for row in outs])
            new_v = jnp.stack([row[2] for row in outs])
            gnorm = jnp.sqrt(fc)
            trust = jnp.ones((), jnp.float32)
        else:
            red = jnp.stack(outs)
            new_pb, new_m, new_v, gnorm, trust = _flat_barrier_update(
                pb, red, state.opt.m, state.opt.v, lr_step, ocfg, lr,
                inv_w=cell["inv_w"], dmask=dmask, segs=segs,
                n_leaves=n_leaves)
        new_params = bkt.unpack_buckets(new_pb, layout)
        new_err = state.err
        if use_err and errs is not None:
            new_err = jnp.stack(errs, axis=1).reshape(ranks, nb, be)
        loss = weighting.finalize(o, w)
        metrics = {"loss": loss, "weight": w, "grad_norm": gnorm,
                   "lr": lr}
        if ocfg.name == "lamb":
            metrics["trust_ratio"] = trust
        new_state = TrainState(
            params=new_params,
            opt=adam.AdamState(step=lr_step, m=new_m, v=new_v),
            err=new_err)
        return new_state, metrics

    return step


# --------------------------------------------------------------------------
# pipeline-parallel step (HetConfig.pipeline_stages > 1)
# --------------------------------------------------------------------------


def _pipe_send(x: jnp.ndarray, mesh: Mesh, spec: P,
               direction: int) -> jnp.ndarray:
    """Move a stage-boundary value to the next (+1) / previous (-1)
    stage along the "pipe" axis.

    Every stage executes the full program in program order on
    pipe-replicated values, so the ring ppermute is value-preserving —
    it exists to hand the runtime the placement edge between
    consecutive stages (the activation / cotangent hop the modeled
    timeline charges to DCN). Without a pipe axis on the mesh it is the
    identity.
    """
    if "pipe" not in mesh.axis_names:
        return x
    n = mesh.shape["pipe"]
    perm = [(i, (i + direction) % n) for i in range(n)]
    return jax.shard_map(
        lambda v: jax.lax.ppermute(v, "pipe", perm),
        mesh=mesh, in_specs=spec, out_specs=spec,
        axis_names={"pipe"}, check_vma=False)(x)


def _pipeline_leaf_pieces(params_shape: Any, cfg: ModelConfig,
                          splan: pipe.StagePlan):
    """Per-leaf ``(offset_within_leaf, n, flush_stage)`` pieces for the
    pipeline's bucket engine (cf. ``_staged_leaf_pieces``).

    Flush stages follow the LAST microbatch's backward completion
    order: the head lands first (flush stage 0), layer ``l`` at the B
    event of its pipeline stage (flush stage ``S - 1 -
    stage_of_layer(l)``), the embedding table last (flush stage ``S`` —
    a tied table also receives a head-stage contribution, so its grad
    is only final at the end). Feeds
    ``core/buckets.py::bucket_readiness``.
    """
    from repro.models import transformer as tr

    L = cfg.num_layers
    S = splan.num_stages
    head_keys = set(tr.head_param_keys(cfg))
    pieces = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            params_shape)[0]:
        n = int(math.prod(leaf.shape)) if leaf.shape else 1
        top = _path_top(path[0])
        if top == "layers":
            if n % L:
                raise ValueError(
                    f"stacked leaf {jax.tree_util.keystr(path)} of "
                    f"{n} elements does not split into {L} layers")
            per = n // L
            pieces.append([(l * per, per,
                            S - 1 - splan.stage_of_layer(l))
                           for l in range(L)])
        elif top == "embed":
            pieces.append([(0, n, S)])
        elif top in head_keys:
            pieces.append([(0, n, 0)])
        else:
            raise ValueError(
                f"pipeline_stages > 1: unexpected param subtree "
                f"'{top}' (uniform stack expects embed / final_norm / "
                f"lm_head / layers)")
    return pieces


def _build_pipeline_step(model: Model, tcfg: TrainConfig, mesh: Mesh, *,
                         splan: pipe.StagePlan,
                         layout: Optional[bkt.BucketLayout]):
    """The pipelined train step: capacity-sized contiguous stages, the
    accumulation microbatches streamed through them in 1F1B (or GPipe)
    program order.

    The step emits one deterministic global sequence of per-stage VJP
    segments (core/pipeline.py::program_order): each F event runs one
    stage's forward slice and hands the boundary activation to the next
    stage through a ``_pipe_send`` region; each B event runs the
    stage's VJP, scatter-adds the stage-slice gradients into the
    accumulator, and sends the input cotangent back. Because every
    stage's B events occur in microbatch order and stage slices are
    disjoint, the per-element gradient accumulation reproduces
    ``accumulate.unrolled_accumulate``'s add order — fp32 with
    ``scan_layers=False`` is bit-identical to pure DP of the same
    config (``pipeline_stages=1``), whatever the stage partition
    (BENCH_pipeline.json invariant).

    Reduction: with ``grad_reduction="allreduce"`` (``layout`` None)
    XLA reduces from the shardings exactly as the monolithic path;
    with ``"bucketed_allreduce"`` the grads live in the flat (ranks,
    padded_total) stream and each stage's buckets flush through their
    own small exchange regions the moment the last microbatch's B event
    for that stage lands (readiness from ``_pipeline_leaf_pieces``) —
    per-stage reduction overlapping the remaining drain, mirroring
    ``overlap="backward"``'s engine. The tree-form optimizer runs after
    the drain (``overlap`` must be "none" with pipelining —
    HetConfig.validate), so moments stay a pytree and checkpoints
    restore bit-exactly across stage plans, including pure DP.

    Exactness on the bucketed path: losses are bit-identical to the
    stages=1 bucketed step, but parameters can drift by 1-2 ulp — XLA
    fuses the attention backward differently once the program is cut at
    a stage boundary (verified: the drift appears for ANY vjp cut
    between layers, including the per-layer granularity, and sits in
    the softmax-backward reduction feeding dq/dk/dv). A documented
    trade like backward-overlap's bf16 carry; the allreduce path above
    carries the bit-exactness claim (BENCH_pipeline.json).
    """
    from repro.models import transformer as tr

    cfg = model.cfg
    ocfg = tcfg.optimizer
    M = max(1, tcfg.het.accum_steps)
    S = splan.num_stages
    ranges = splan.stage_ranges()
    events = pipe.program_order(S, M, schedule=tcfg.het.pipeline_schedule)
    dp = mesh_dp_axes(mesh)
    n_dp = dp_size(mesh)
    token_frontend = cfg.frontend == "token"
    L = cfg.num_layers

    def carry_dtype(p):
        # same bf16 passthrough as compute_grads' accumulation carry
        return p.dtype if p.dtype == jnp.bfloat16 else jnp.float32

    if layout is None:
        # ---- plain-SPMD path (grad_reduction="allreduce") ------------
        ctx = make_parallel_ctx(mesh)
        seg = tr.pipeline_stage_fns(cfg, ctx, ranges,
                                    label_smoothing=tcfg.label_smoothing)
        embed_fn, head_fn = seg["embed_fn"], seg["head_fn"]
        head_keys, stage_fwd = seg["head_keys"], seg["stage_fwd"]
        act_spec = shr.stage_activation_spec(
            mesh, tcfg.shape.global_batch // M)

        def step(state: TrainState, batch: Dict
                 ) -> Tuple[TrainState, Dict]:
            lr_step = state.opt.step + 1
            lr = schedules.learning_rate(ocfg, lr_step)
            params = state.params
            split = acc.split_microbatches(batch, M, num_ranks=n_dp)
            mbs = [jax.tree.map(lambda a: a[i], split) for i in range(M)]
            slices = [jax.tree.map(lambda a: a[r0:r1], params["layers"])
                      for (r0, r1) in ranges]
            emb_p = {"embed": params["embed"]} if token_frontend else {}
            hp = {k: params[k] for k in head_keys}
            g_acc = jax.tree.map(
                lambda p: jnp.zeros(p.shape, carry_dtype(p)), params)
            o_acc = jnp.zeros((), jnp.float32)
            w_acc = jnp.zeros((), jnp.float32)
            x_in: Dict = {}
            vjps: Dict = {}
            head_vjps: Dict = {}
            embed_vjps: Dict = {}
            cots: Dict = {}
            w_sgs: Dict = {}
            head_emb: Dict = {}
            for (s, kind, m) in events:
                mb = mbs[m]
                if kind == pipe.FWD:
                    if s == 0:
                        if token_frontend:
                            x0, evjp = jax.vjp(
                                lambda q: embed_fn(q, mb["inputs"]),
                                emb_p)
                            embed_vjps[m] = evjp
                        else:
                            x0 = embed_fn(emb_p, mb["inputs"])
                        xa = (x0, jnp.zeros((), jnp.float32))
                    else:
                        xa = x_in.pop((s, m))
                    positions = jnp.arange(xa[0].shape[-2])
                    (x_out, a_out), vjp = jax.vjp(
                        lambda q, xx, aa: stage_fwd[s](q, xx, aa,
                                                       positions),
                        slices[s], xa[0], xa[1])
                    vjps[(s, m)] = vjp
                    if s < S - 1:
                        x_in[(s + 1, m)] = (
                            _pipe_send(x_out, mesh, act_spec, +1),
                            a_out)
                    else:
                        (ce, w), hvjp = jax.vjp(
                            lambda q, xx: head_fn(q, xx, mb["labels"],
                                                  mb["weights"]),
                            hp, x_out)
                        w_sg = jax.lax.stop_gradient(w)
                        o_acc = o_acc + (ce + a_out * w_sg)
                        w_acc = w_acc + w
                        head_vjps[m] = hvjp
                        w_sgs[m] = w_sg
                else:
                    if s == S - 1:
                        g_hp, x_cot = head_vjps.pop(m)(
                            (jnp.ones((), jnp.float32),
                             jnp.zeros((), jnp.float32)))
                        for key in head_keys:
                            if key == "embed":
                                # tied table: held until the stage-0 B
                                # event and combined with the gather
                                # cotangent there — ONE add per
                                # microbatch, the monolithic VJP's
                                # association
                                head_emb[m] = g_hp["embed"]
                                continue
                            g_acc[key] = jax.tree.map(
                                lambda a, b: a + b.astype(a.dtype),
                                g_acc[key], g_hp[key])
                        cot = (x_cot, w_sgs[m])
                    else:
                        cot = cots.pop((s, m))
                    g_sl, x_cot, a_cot = vjps.pop((s, m))(cot)
                    r0 = ranges[s][0]
                    g_acc["layers"] = jax.tree.map(
                        lambda a, g: a.at[r0:r0 + g.shape[0]].add(
                            g.astype(a.dtype)),
                        g_acc["layers"], g_sl)
                    if s > 0:
                        cots[(s - 1, m)] = (
                            _pipe_send(x_cot, mesh, act_spec, -1),
                            a_cot)
                    elif token_frontend:
                        g_emb = embed_vjps.pop(m)(x_cot)[0]["embed"]
                        if m in head_emb:
                            g_emb = g_emb + head_emb.pop(m)
                        g_acc["embed"] = g_acc["embed"] + \
                            g_emb.astype(g_acc["embed"].dtype)
            loss = weighting.finalize(o_acc, w_acc)
            grads = weighting.scale_grads(g_acc, w_acc)
            opt_apply = (lamb.apply_update if ocfg.name == "lamb"
                         else adam.apply_update)
            new_params, opt, met = opt_apply(params, grads, state.opt,
                                             ocfg, lr)
            metrics = {"loss": loss, "weight": w_acc, **met}
            return TrainState(params=new_params, opt=opt,
                              err=state.err), metrics

        return step

    # ---- bucketed path (grad_reduction="bucketed_allreduce") ---------
    # rank-major vmapped stage VJPs with the flat f32 gradient stream;
    # per-stage bucket flushes through small manual exchange regions
    # (cf. _build_backward_overlap_step — same engine, pipeline order)
    inner_ctx = ParallelCtx(mesh=mesh, dp_axes=(), tp_axis=tp_axis(mesh))
    seg = tr.pipeline_stage_fns(cfg, inner_ctx, ranges,
                                label_smoothing=tcfg.label_smoothing)
    embed_fn, head_fn = seg["embed_fn"], seg["head_fn"]
    head_keys, stage_fwd = seg["head_keys"], seg["stage_fwd"]
    ranks = n_dp
    red_axis: Any = dp if len(dp) > 1 else dp[0]
    axis_set = set(dp)
    rank_spec = P(dp)
    buf_spec = P(dp if len(dp) > 1 else dp[0])
    be = layout.bucket_elems
    shard = be // ranks
    q_impl = tcfg.het.quantize_impl
    params_shape = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    readiness = bkt.bucket_readiness(
        layout, _pipeline_leaf_pieces(params_shape, cfg, splan))
    subtree_slots: Dict[str, list] = {}
    for (path, _), off, size in zip(
            jax.tree_util.tree_flatten_with_path(params_shape)[0],
            layout.offsets, layout.sizes):
        subtree_slots.setdefault(_path_top(path[0]), []).append(
            (off, size))

    def scatter_subtree(buf, top, grads, layers=None):
        """Scatter-add a landed grad subtree into the stream buffer
        (stage slices index a contiguous per-layer region)."""
        leaves = jax.tree.leaves(grads)
        slots = subtree_slots.get(top, [])
        assert len(leaves) == len(slots), (top, len(leaves), len(slots))
        for g, (off, size) in zip(leaves, slots):
            if layers is not None:
                r0, r1 = layers
                per = size // L
                off, size = off + r0 * per, (r1 - r0) * per
            buf = buf.at[:, off:off + size].add(
                g.reshape(ranks, size).astype(jnp.float32))
        return buf

    def split_rank_microbatches(sb):
        """Per-rank accumulation split (inner_dp == 1 counterpart of
        the backward-overlap splitter — rows per rank cut into M equal
        contiguous microbatch slices)."""
        if M == 1:
            return [sb]

        def split(a):
            b = a.shape[1]
            if b % M:
                raise ValueError(
                    f"rows {b} per reduction rank not divisible by "
                    f"accum {M}")
            return a.reshape(ranks, M, b // M, *a.shape[2:])

        s = {k: split(v) for k, v in sb.items()}
        return [jax.tree.map(lambda a: a[:, i], s) for i in range(M)]

    def step(state: TrainState, batch: Dict) -> Tuple[TrainState, Dict]:
        lr_step = state.opt.step + 1
        lr = schedules.learning_rate(ocfg, lr_step)
        params = state.params
        sb = jax.tree.map(
            lambda v: jax.lax.with_sharding_constraint(
                v.reshape(ranks, v.shape[0] // ranks, *v.shape[1:]),
                rank_spec), batch)
        mbs = split_rank_microbatches(sb)
        slices = [jax.tree.map(lambda a: a[r0:r1], params["layers"])
                  for (r0, r1) in ranges]
        emb_p = {"embed": params["embed"]} if token_frontend else {}
        hp = {k: params[k] for k in head_keys}

        def prep(k, raw_k):
            return raw_k.reshape(ranks, ranks, shard), None

        def exchange(k, prepared):
            payload, _ = prepared

            def region(pl):
                onehot = bkt.rank_onehot(red_axis, ranks)
                red, _ = bkt.exchange_prepared_bucket(
                    pl[0], None, axis=red_axis, axis_size=ranks,
                    compress=False, block_size=_BLOCK, impl=q_impl,
                    interpret=False, onehot=onehot)
                return red

            red = jax.shard_map(
                region, mesh=mesh, in_specs=buf_spec, out_specs=P(),
                axis_names=axis_set, check_vma=False)(payload)
            return red, None

        pipeline_fl = bkt.BucketFlushPipeline(readiness, prep, exchange)

        def flush(stage, buf):
            pipeline_fl.flush_ready_buckets(
                stage, lambda k: buf[:, k * be:(k + 1) * be])

        buf = jax.lax.with_sharding_constraint(
            jnp.zeros((ranks, layout.padded_total), jnp.float32),
            buf_spec)
        o_acc = jnp.zeros((ranks,), jnp.float32)
        w_acc = jnp.zeros((ranks,), jnp.float32)
        x_in: Dict = {}
        stage_in: Dict = {}
        head_in: Dict = {}
        cots: Dict = {}
        w_sgs: Dict = {}
        head_emb: Dict = {}
        for (s, kind, m) in events:
            mb = mbs[m]
            if kind == pipe.FWD:
                if s == 0:
                    x0 = jax.vmap(embed_fn, in_axes=(None, 0))(
                        emb_p, mb["inputs"])
                    xa = (x0, jnp.zeros((ranks,), jnp.float32))
                else:
                    xa = x_in.pop((s, m))
                stage_in[(s, m)] = xa
                positions = jnp.arange(xa[0].shape[-2])
                x_out, a_out = jax.vmap(
                    lambda sl_, x_, a_: stage_fwd[s](sl_, x_, a_,
                                                     positions),
                    in_axes=(None, 0, 0))(slices[s], *xa)
                if s < S - 1:
                    x_in[(s + 1, m)] = (
                        _pipe_send(x_out, mesh, rank_spec, +1), a_out)
                else:
                    ce, w = jax.vmap(
                        head_fn, in_axes=(None, 0, 0, 0))(
                        hp, x_out, mb["labels"], mb["weights"])
                    w_sg = jax.lax.stop_gradient(w)
                    o_acc = o_acc + (ce + a_out * w_sg)
                    w_acc = w_acc + w
                    head_in[m] = x_out
                    w_sgs[m] = w_sg
            else:
                if s == S - 1:
                    def head_stage(hp_, x_l, lab, wt):
                        _, vjp = jax.vjp(
                            lambda q, xx: head_fn(q, xx, lab, wt),
                            hp_, x_l)
                        return vjp((jnp.ones((), jnp.float32),
                                    jnp.zeros((), jnp.float32)))

                    g_hp, x_cot = jax.vmap(
                        head_stage, in_axes=(None, 0, 0, 0))(
                        hp, head_in.pop(m), mb["labels"],
                        mb["weights"])
                    for key in head_keys:
                        if key == "embed":
                            # tied table: one add per microbatch at the
                            # stage-0 B event (see the allreduce path)
                            head_emb[m] = g_hp["embed"]
                            continue
                        buf = scatter_subtree(buf, key, g_hp[key])
                    cot = (x_cot, w_sgs[m])
                else:
                    cot = cots.pop((s, m))
                xa = stage_in.pop((s, m))
                positions = jnp.arange(xa[0].shape[-2])

                def stage_bwd(sl_, x_, a_, xc, ac):
                    _, vjp = jax.vjp(
                        lambda q, xx, aa: stage_fwd[s](q, xx, aa,
                                                       positions),
                        sl_, x_, a_)
                    return vjp((xc, ac))

                g_sl, x_cot, a_cot = jax.vmap(
                    stage_bwd, in_axes=(None, 0, 0, 0, 0))(
                    slices[s], xa[0], xa[1], cot[0], cot[1])
                buf = scatter_subtree(buf, "layers", g_sl,
                                      layers=ranges[s])
                if m == M - 1:
                    flush(S - 1 - s, buf)
                if s > 0:
                    cots[(s - 1, m)] = (
                        _pipe_send(x_cot, mesh, rank_spec, -1), a_cot)
                else:
                    if token_frontend:
                        def embed_stage(ep, i, xc):
                            _, vjp = jax.vjp(
                                lambda q: embed_fn(q, i), ep)
                            return vjp(xc)[0]

                        g_emb = jax.vmap(
                            embed_stage, in_axes=(None, 0, 0))(
                            emb_p, mb["inputs"], x_cot)["embed"]
                        if m in head_emb:
                            g_emb = g_emb + head_emb.pop(m)
                        buf = scatter_subtree(buf, "embed", g_emb)
                    if m == M - 1:
                        flush(S, buf)
        outs, _, _ = pipeline_fl.finish()
        red = jnp.stack(outs)
        grads = bkt.unpack_buckets(red, layout)
        o, w = jnp.sum(o_acc), jnp.sum(w_acc)
        loss = weighting.finalize(o, w)
        grads = weighting.scale_grads(grads, w)
        opt_apply = (lamb.apply_update if ocfg.name == "lamb"
                     else adam.apply_update)
        new_params, opt, met = opt_apply(params, grads, state.opt,
                                         ocfg, lr)
        metrics = {"loss": loss, "weight": w, **met}
        return TrainState(params=new_params, opt=opt,
                          err=state.err), metrics

    return step


# --------------------------------------------------------------------------
# train step
# --------------------------------------------------------------------------


def _fed_batch_specs(cfg: ModelConfig, tcfg: TrainConfig,
                     mesh: Mesh) -> Dict[str, P]:
    """Specs of the batch ``launch/train.py`` feeds the step.

    Packed batches hold one capacity-plan buffer per DP rank
    (``buffer_rows * dp_size`` rows), so they always shard over the DP
    axes — even when the global batch does not divide the DP size, as
    uneven row shares make common. Canonical batches are the
    ``global_batch`` rows in global order.
    """
    rows = (tcfg.shape.global_batch if tcfg.het.weighting == "canonical"
            else dp_size(mesh))
    return shr.batch_specs(cfg, mesh, rows)


def _counted(jitted, gauge: str, count: Callable[[str], int]):
    """The jitted step, compiled ahead of its first call so that the one
    executable the calls run is also the one that is counted: ``gauge``
    (``obs.gauge``) is set to ``count`` of its optimized HLO text.

    A call with other argument types than the first goes through the
    jit, which traces and compiles for them; ``_cache_size()`` counts
    those executables with the first, as a jit's own does, so that the
    serving engine's retrace guard still sees a second compile."""
    compiled = None

    def step(*args):
        nonlocal compiled
        if compiled is None:
            compiled = jitted.lower(*args).compile()
            obs.gauge(gauge, count(compiled.as_text()))
        try:
            return compiled(*args)
        except TypeError:          # argument types it was not built for
            return jitted(*args)

    step.lower = jitted.lower
    step._cache_size = lambda: (compiled is not None) + jitted._cache_size()
    return step


def build_train_step(model: Model, tcfg: TrainConfig, mesh: Mesh
                     ) -> Callable[[TrainState, Dict], Tuple[TrainState,
                                                             Dict]]:
    validate_train_config(model, tcfg, mesh)
    cfg = model.cfg
    ctx = make_parallel_ctx(mesh)
    ocfg = tcfg.optimizer
    accum = max(1, tcfg.het.accum_steps)
    hier = (tcfg.het.grad_reduction == "hierarchical"
            and "pod" in mesh.axis_names)
    bucketed_ar = tcfg.het.grad_reduction == "bucketed_allreduce"
    compress = tcfg.het.compression if hier else "none"
    layout = bucket_layout(model, tcfg, mesh) if (hier or bucketed_ar) \
        else None
    # bucketed_ar always has a layout here: validate_train_config
    # raised on a missing DP axis, HetConfig.validate on bucket_mb <= 0
    use_err = _err_enabled(tcfg, mesh)
    q_impl = tcfg.het.quantize_impl
    n_dp = dp_size(mesh)
    dp = mesh_dp_axes(mesh)
    n_pods = mesh.shape["pod"] if "pod" in mesh.axis_names else 1
    overlap = _overlap_enabled(tcfg, mesh)
    if overlap and layout is None:
        raise ValueError("HetConfig.overlap='buckets' needs a bucket "
                         "layout (bucket_mb > 0 and reduction axes)")
    # the fused per-bucket pipeline can stream the optimizer as each
    # bucket lands — AdamW entirely, LAMB up to one trailing
    # trust-ratio pass (optim/lamb.py); global-norm clipping needs
    # every bucket BEFORE the first moment update, so it keeps the
    # pipelined exchange but updates behind a barrier
    fused_stream = overlap and ocfg.grad_clip <= 0

    if tcfg.het.pipeline_stages > 1:
        # capacity-sized pipeline stages with 1F1B microbatching.
        # HetConfig.validate pinned overlap="none" and reduction to
        # allreduce / bucketed_allreduce, so `layout` is exactly the
        # bucket grid for the per-stage flushes (or None for plain
        # allreduce) and the optimizer state stays a pytree
        splan = stage_plan_for(model, tcfg)
        pipe_step = _build_pipeline_step(model, tcfg, mesh, splan=splan,
                                         layout=layout)
        specs = state_specs(model, tcfg, mesh)
        bspecs = _fed_batch_specs(cfg, tcfg, mesh)
        return _counted(jax.jit(
            pipe_step,
            in_shardings=(shr.named(mesh, specs),
                          shr.named(mesh, bspecs)),
            out_shardings=(shr.named(mesh, specs), None),
            donate_argnums=(0,),
        ), "train.exchange_bytes", exchange_bytes)

    if overlap and tcfg.het.overlap == "backward":
        # staged layer-by-layer backward with in-backprop bucket
        # flushes — built as its own step function (the schedule is a
        # top-level interleaving of vmapped VJP stages and per-bucket
        # exchange regions, not a shard_map-wrapped monolith)
        bwd_step = _build_backward_overlap_step(
            model, tcfg, mesh, layout=layout, hier=hier,
            compress=compress, use_err=use_err,
            fused_stream=fused_stream)
        specs = state_specs(model, tcfg, mesh)
        bspecs = _fed_batch_specs(cfg, tcfg, mesh)
        return _counted(jax.jit(
            bwd_step,
            in_shardings=(shr.named(mesh, specs),
                          shr.named(mesh, bspecs)),
            out_shardings=(shr.named(mesh, specs), None),
            donate_argnums=(0,),
        ), "train.exchange_bytes", exchange_bytes)

    # inside a manual region the manual axes must not appear in sharding
    # constraints — hierarchical keeps "data" automatic inside the pod
    # region; bucketed_allreduce makes the whole DP set manual
    if hier:
        inner_ctx = ParallelCtx(mesh=mesh, dp_axes=("data",),
                                tp_axis=tp_axis(mesh))
        inner_dp = n_dp // n_pods
    elif bucketed_ar:
        inner_ctx = ParallelCtx(mesh=mesh, dp_axes=(),
                                tp_axis=tp_axis(mesh))
        inner_dp = 1
    else:
        inner_ctx = ctx
        inner_dp = n_dp

    def compute_grads(params, batch):
        """Returns (grad_of_sums, obj_sum, weight_sum) — unscaled."""
        def objective(p, b):
            o, w, _ = model.loss_fn(
                p, b, inner_ctx, label_smoothing=tcfg.label_smoothing)
            return o, w

        def grad_fn(p, b):
            # value_and_grad, split so that the device trace names the
            # forward and the backward pass apart
            with jax.named_scope("forward"):
                o, pullback, w = jax.vjp(lambda q: objective(q, b), p,
                                         has_aux=True)
            with jax.named_scope("backward"):
                g, = pullback(jnp.ones_like(o))
            return (o, w), g

        if accum == 1:
            (o, w), g = grad_fn(params, batch)
            return g, o, w
        mbs = acc.split_microbatches(batch, accum, num_ranks=inner_dp)

        # accumulation carry dtype: fp32, except when params are stored
        # bf16 (arctic/deepseek giants) where an fp32 carry alone would
        # blow the 16 GB budget — bf16 carry, documented in EXPERIMENTS
        def carry_dtype(p):
            return p.dtype if p.dtype == jnp.bfloat16 else jnp.float32

        if not cfg.scan_layers:
            # unrolled-program class (scan_layers=False, required by
            # overlap="backward"): keep the accumulation unrolled too
            # so the staged backward stays bit-identical at accum > 1
            return acc.unrolled_accumulate(grad_fn, params, mbs,
                                           carry_dtype=carry_dtype)
        return acc.scan_accumulate(grad_fn, params, mbs,
                                   carry_dtype=carry_dtype)

    def apply_pod_reduce(g, err):
        """The cross-pod leg: bucketed engine or legacy per-leaf walk."""
        if layout is not None:
            g, ne = _reduce_bucketed(
                g, err if use_err else None, axis="pod",
                axis_size=n_pods, compress=compress, layout=layout,
                impl=q_impl)
            return g, (ne if ne is not None else ())
        return _cross_pod_reduce(g, err, compress, n_pods)

    # ---- fused overlap step (HetConfig.overlap="buckets") ---------------
    # The optimizer moves INSIDE the manual region: the per-bucket
    # pipeline exchanges bucket k while bucket k+1 quantizes, and the
    # flat-view AdamW update for bucket k runs the moment it lands.
    # The packed moments enter/leave the region replicated over the
    # reduction axes; every rank computes the identical update.
    if overlap:
        dmask = bkt.decay_mask(layout)
        segs = bkt.segment_ids(layout) if ocfg.name == "lamb" else None
        n_leaves = len(layout.sizes)
        red_axis: Any = "pod" if hier else (dp if len(dp) > 1 else dp[0])
        red_size = n_pods if hier else n_dp

        def fused_reduce_update(g, params, m, v, e, w_sum, lr_step, lr):
            """Inside shard_map(manual over the reduction axes).

            ``g``: this rank's unreduced grad tree; ``e``: this rank's
            (nb, be) error slice or None; ``w_sum``: the GLOBAL weight
            sum. Returns (params', m', v', err'(nb, be) | None, gnorm,
            mean trust ratio — 1.0 for AdamW).
            """
            gb = bkt.pack_buckets(g, layout)
            pb = bkt.pack_buckets(params, layout)
            inv_w = 1.0 / jnp.maximum(w_sum, 1e-9)
            kwargs = dict(axis=red_axis, axis_size=red_size,
                          compress=(compress != "none"),
                          block_size=_BLOCK, impl=q_impl)
            if fused_stream and ocfg.name != "lamb":
                def hook(ssq, red_k, xs_k, k):
                    p_k, m_k, v_k, dm_k = xs_k
                    g_k = red_k * inv_w
                    out = adam.apply_update_flat(
                        p_k, g_k, m_k, v_k, lr_step, ocfg, lr,
                        decay_mask=dm_k)
                    return ssq + jnp.sum(g_k * g_k), out

                outs, new_e, ssq = bkt.exchange_buckets_overlapped(
                    gb, e, bucket_fn=hook,
                    fn_carry=jnp.zeros((), jnp.float32),
                    bucket_xs=(pb, m, v, dmask), **kwargs)
                new_pb, new_m, new_v = outs
                gnorm = jnp.sqrt(ssq)
                trust = jnp.ones((), jnp.float32)
            else:
                # clip barrier, and ALL of LAMB in this after-backward
                # engine: fusing LAMB's hook into the per-bucket scan
                # deterministically perturbs how XLA compiles the
                # whole-module gradient/reduction program (~0.4% of
                # reduced-grad elements move 1 ulp, measured across
                # every hook/optimization_barrier variant), which
                # breaks the backward==buckets bitwise contract
                # (tests/test_overlap.py). The backward-overlap flush
                # pipeline streams LAMB bitwise-safely; here the
                # barrier form is the bit-exact choice — and the
                # exchange is already fully overlapped bucket-to-
                # bucket, so only the optimizer pass trails.
                red, new_e, _ = bkt.exchange_buckets_overlapped(
                    gb, e, **kwargs)
                new_pb, new_m, new_v, gnorm, trust = \
                    _flat_barrier_update(
                        pb, red, m, v, lr_step, ocfg, lr, inv_w=inv_w,
                        dmask=dmask, segs=segs, n_leaves=n_leaves)
            return (bkt.unpack_buckets(new_pb, layout), new_m, new_v,
                    new_e, gnorm, trust)

        def overlap_step(state: TrainState, batch: Dict
                         ) -> Tuple[TrainState, Dict]:
            lr_step = state.opt.step + 1
            lr = schedules.learning_rate(ocfg, lr_step)
            err_in = state.err if use_err else ()
            err_spec = P("pod") if use_err else P()
            axes = {"pod"} if hier else set(dp)
            batch_spec = P("pod") if hier else P(dp)

            def unslice_err(err):
                return (err.reshape(layout.num_buckets,
                                    layout.bucket_elems)
                        if use_err else None)

            def reslice_err(new_e, err):
                return (new_e.reshape(1, layout.num_buckets,
                                      layout.bucket_elems)
                        if use_err else err)

            pspecs_in = state_specs(model, tcfg, mesh).params

            def local(params, b, err, m, v, step_no, lr_in):
                g, o, w = compute_grads(params, b)
                if hier:
                    # re-pin lost (data, model) layouts (see the
                    # hierarchical branch below)
                    g = jax.tree.map(
                        lambda gr, s:
                        jax.lax.with_sharding_constraint(gr, s),
                        g, pspecs_in)
                o = jax.lax.psum(o, red_axis)
                w = jax.lax.psum(w, red_axis)
                np_, nm, nv, ne, gn, tr = fused_reduce_update(
                    g, params, m, v, unslice_err(err), w,
                    step_no, lr_in)
                return (np_, nm, nv, reslice_err(ne, err), o, w,
                        gn, tr)

            (new_params, new_m, new_v, new_err, o, w, gnorm,
             trust) = jax.shard_map(
                local, mesh=mesh,
                in_specs=(P(), batch_spec, err_spec, P(), P(),
                          P(), P()),
                out_specs=(P(), P(), P(), err_spec, P(), P(),
                           P(), P()),
                axis_names=axes, check_vma=False,
            )(state.params, batch, err_in, state.opt.m,
              state.opt.v, lr_step, lr)
            loss = weighting.finalize(o, w)
            metrics = {"loss": loss, "weight": w, "grad_norm": gnorm,
                       "lr": lr}
            if ocfg.name == "lamb":
                metrics["trust_ratio"] = trust
            new_state = TrainState(
                params=new_params,
                opt=adam.AdamState(step=lr_step, m=new_m, v=new_v),
                err=new_err if use_err else state.err)
            return new_state, metrics

    canonical = tcfg.het.weighting == "canonical"

    def canonical_step(state: TrainState, batch: Dict
                       ) -> Tuple[TrainState, Dict]:
        """Order-canonical executor (core/weighting.py), now a real
        train-step mode instead of bench-only: per-row vmapped grads
        summed along the global-row axis with ONE fixed reduction tree.
        The row->rank partition drops out of the float math entirely,
        so two runs consuming the same global rows are bit-identical
        whatever capacity replans happened in between — provided the
        sampler emits rows in canonical global order
        (HetSampler(canonical_order=True))."""
        def row_loss(p, b):
            return model.loss_fn(p, b,
                                 label_smoothing=tcfg.label_smoothing)

        (o_r, w_r), g_r = weighting.per_row_values(
            row_loss, state.params, batch)
        loss, grads, _, w = weighting.canonical_aggregate(o_r, w_r, g_r)
        lr = schedules.learning_rate(ocfg, state.opt.step + 1)
        opt_apply = (lamb.apply_update if ocfg.name == "lamb"
                     else adam.apply_update)
        params, opt, met = opt_apply(state.params, grads,
                                     state.opt, ocfg, lr)
        metrics = {"loss": loss, "weight": w, **met}
        return TrainState(params=params, opt=opt, err=state.err), metrics

    def train_step(state: TrainState, batch: Dict
                   ) -> Tuple[TrainState, Dict]:
        if canonical:
            return canonical_step(state, batch)
        if overlap:
            return overlap_step(state, batch)
        if hier:
            pspecs_in = state_specs(model, tcfg, mesh).params

            def pod_local(params, b, err):
                g, o, w = compute_grads(params, b)
                # inside the partially-manual region XLA's sharding
                # propagation can lose the (data, model) layout of
                # the gradients; re-pin them to the param specs so
                # the pod exchange moves shards, not replicated
                # leaves
                g = jax.tree.map(
                    lambda gr, s: jax.lax.with_sharding_constraint(
                        gr, s),
                    g, pspecs_in)
                with jax.named_scope("exchange"):
                    g, ne = apply_pod_reduce(g, err)
                    return g, jax.lax.psum(o, "pod"), \
                        jax.lax.psum(w, "pod"), ne

            grads, o, w, new_err = jax.shard_map(
                pod_local, mesh=mesh,
                in_specs=(P(), P("pod"), P("pod") if use_err
                          else P()),
                out_specs=(P(), P(), P(), P("pod") if use_err
                           else P()),
                axis_names={"pod"}, check_vma=False,
            )(state.params, batch, state.err)
        elif bucketed_ar:
            axis = dp if len(dp) > 1 else dp[0]

            def reduce_buckets(g):
                out, _ = _reduce_bucketed(g, None, axis=axis,
                                          axis_size=n_dp,
                                          compress="none", layout=layout,
                                          impl=q_impl)
                return out

            def dp_local(params, b):
                g, o, w = compute_grads(params, b)
                with jax.named_scope("exchange"):
                    return reduce_buckets(g), jax.lax.psum(o, dp), \
                        jax.lax.psum(w, dp)

            grads, o, w = jax.shard_map(
                dp_local, mesh=mesh,
                in_specs=(P(), P(dp)),
                out_specs=(P(), P(), P()),
                axis_names=set(dp), check_vma=False,
            )(state.params, batch)
            new_err = state.err
        else:
            grads, o, w = compute_grads(state.params, batch)
            new_err = state.err
        with jax.named_scope("exchange"):
            # the weighted mean over every rank's rows (with plain
            # allreduce the partitioner places the sums' collectives)
            loss = weighting.finalize(o, w)
            grads = weighting.scale_grads(grads, w)
        with jax.named_scope("optimizer"):
            lr = schedules.learning_rate(ocfg, state.opt.step + 1)
            opt_apply = (lamb.apply_update if ocfg.name == "lamb"
                         else adam.apply_update)
            params, opt, met = opt_apply(state.params, grads,
                                         state.opt, ocfg, lr)
        metrics = {"loss": loss, "weight": w, **met}
        return TrainState(params=params, opt=opt, err=new_err), metrics

    specs = state_specs(model, tcfg, mesh)
    bspecs = _fed_batch_specs(cfg, tcfg, mesh)
    return _counted(jax.jit(
        train_step,
        in_shardings=(shr.named(mesh, specs), shr.named(mesh, bspecs)),
        out_shardings=(shr.named(mesh, specs), None),
        donate_argnums=(0,),
    ), "train.exchange_bytes", exchange_bytes)


# --------------------------------------------------------------------------
# serve steps
# --------------------------------------------------------------------------


def build_prefill_step(model: Model, shape: ShapeConfig, mesh: Mesh):
    cfg = model.cfg
    ctx = make_parallel_ctx(mesh)

    def prefill(params, inputs):
        return model.prefill(params, inputs, ctx, max_len=shape.seq_len)

    params_shape = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    pspecs = shr.param_specs(cfg, params_shape, mesh)
    dp = mesh_dp_axes(mesh)
    b = shape.global_batch
    bspec = dp if b % dp_size(mesh) == 0 else None
    in_spec = (P(bspec, None, None) if cfg.frontend != "token"
               else P(bspec, None))
    cache_shape = jax.eval_shape(
        functools.partial(model.init_cache, b, shape.seq_len))
    cspecs = shr.cache_specs(cfg, cache_shape, mesh, b)
    logit_spec = shr.fit_spec((b, cfg.vocab_size), P(bspec, "model"), mesh)
    return jax.jit(
        prefill,
        in_shardings=(shr.named(mesh, pspecs),
                      NamedSharding(mesh, in_spec)),
        out_shardings=(NamedSharding(mesh, logit_spec),
                       shr.named(mesh, cspecs)),
    )


def build_decode_step(model: Model, shape: ShapeConfig, mesh: Mesh):
    cfg = model.cfg
    ctx = make_parallel_ctx(mesh)

    def decode(params, tokens, cache, pos):
        return model.decode(params, tokens, cache, pos, ctx)

    params_shape = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    pspecs = shr.param_specs(cfg, params_shape, mesh)
    dp = mesh_dp_axes(mesh)
    b = shape.global_batch
    bspec = dp if b % dp_size(mesh) == 0 else None
    tok_spec = (P(bspec, None) if cfg.frontend != "token" else P(bspec))
    cache_shape = jax.eval_shape(
        functools.partial(model.init_cache, b, shape.seq_len))
    cspecs = shr.cache_specs(cfg, cache_shape, mesh, b)
    logit_spec = shr.fit_spec((b, cfg.vocab_size), P(bspec, "model"), mesh)
    return jax.jit(
        decode,
        in_shardings=(shr.named(mesh, pspecs),
                      NamedSharding(mesh, tok_spec),
                      shr.named(mesh, cspecs), None),
        out_shardings=(NamedSharding(mesh, logit_spec),
                       shr.named(mesh, cspecs)),
        donate_argnums=(2,),
    )


# --------------------------------------------------------------------------
# paged serving steps (continuous batching, repro.serve)
# --------------------------------------------------------------------------


def serve_batch_spec(batch: int, mesh: Mesh, what: str):
    """DP batch spec for a serving step — warns LOUDLY on fallback.

    When ``batch`` is not divisible by the DP extent the arrays are
    fully replicated: every rank embeds/unembeds the whole batch and
    the DP axes do no work. That is a silent multi-x serving-throughput
    loss, so it is worth a warning, not a comment (the old static
    driver fell back without a word). Pick batch/slots as a multiple
    of prod(devices[:-1]) to shard.

    Once-per-build contract: this runs ONLY inside
    ``build_paged_prefill_step`` / ``build_paged_decode_step`` (outside
    the jitted functions they return), so the warning fires once per
    step build, never once per decode step — a serve loop is thousands
    of steps and a per-step warning would bury the log. Pinned by
    tests/test_serve.py::test_serve_batch_spec_warns_once_per_build.
    """
    dp = mesh_dp_axes(mesh)
    if batch % dp_size(mesh) == 0:
        return dp
    logger.warning(
        "%s batch %d is not divisible by the DP extent %d of mesh %s — "
        "falling back to FULLY-REPLICATED batch sharding (every rank "
        "computes the whole batch; data-parallel ranks add no serving "
        "throughput). Use a batch that is a multiple of the DP extent.",
        what, batch, dp_size(mesh), tuple(mesh.shape.items()))
    return None


def build_paged_prefill_step(model: Model, mesh: Mesh, layout,
                             bucket_len: int, batch: int):
    """Jit one prefill bucket: (params, prompts (Bp, Lb), lens (Bp,),
    paged_cache, block_tables (Bp, MB)) -> (logits (Bp, V), cache).

    The pool cache is donated (argnum 3): prefill scatters into it in
    place instead of copying the whole pool per admitted group.
    """
    cfg = model.cfg
    ctx = make_parallel_ctx(mesh)

    def paged_prefill_step(params, prompts, lens, cache, tables):
        return model.prefill_paged(params, prompts, lens, cache, tables,
                                   ctx)

    params_shape = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    pspecs = shr.param_specs(cfg, params_shape, mesh)
    bspec = serve_batch_spec(batch, mesh, "prefill")
    cache_shape = jax.eval_shape(
        functools.partial(model.init_paged_cache, layout))
    cspecs = shr.paged_cache_specs(cfg, cache_shape, mesh)
    logit_spec = shr.fit_spec((batch, cfg.vocab_size), P(bspec, "model"),
                              mesh)
    return jax.jit(
        paged_prefill_step,
        in_shardings=(shr.named(mesh, pspecs),
                      NamedSharding(mesh, P(bspec, None)),
                      NamedSharding(mesh, P(bspec)),
                      shr.named(mesh, cspecs),
                      NamedSharding(mesh, P(bspec, None))),
        out_shardings=(NamedSharding(mesh, logit_spec),
                       shr.named(mesh, cspecs)),
        donate_argnums=(3,),
    )


def build_paged_decode_step(model: Model, mesh: Mesh, layout,
                            slots: int):
    """Jit the continuous decode step: (params, tokens (D,), paged_cache,
    block_tables (D, MB), kv_lens (D,)) -> (logits (D, V), cache).

    One fixed shape for the whole serve loop — per-sequence depth lives
    in ``kv_lens``, membership in the block tables — so the engine can
    assert the function never retraces. The pool is donated (argnum 2)
    and the output pool aliases it; the layer scan carries the whole
    pool and each layer scatters its tokens into it in place
    (``transformer.decode_step_paged``), so no layer's pool is sliced,
    restacked or copied. The step is compiled ahead of its first call
    (``_counted``) and ``serve.pool_move_bytes`` (``obs.gauge``) set to
    the bytes that executable moves in copies, slices and updates that
    hold one layer's pool slab or more (``roofline.hlo.pool_move_bytes``):
    0 when the pool stays in place.
    """
    cfg = model.cfg
    ctx = make_parallel_ctx(mesh)

    def paged_decode_step(params, tokens, cache, tables, kv_lens):
        return model.decode_paged(params, tokens, cache, tables, kv_lens,
                                  ctx)

    params_shape = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    pspecs = shr.param_specs(cfg, params_shape, mesh)
    bspec = serve_batch_spec(slots, mesh, "decode")
    cache_shape = jax.eval_shape(
        functools.partial(model.init_paged_cache, layout))
    cspecs = shr.paged_cache_specs(cfg, cache_shape, mesh)
    logit_spec = shr.fit_spec((slots, cfg.vocab_size), P(bspec, "model"),
                              mesh)
    cshard = shr.named(mesh, cspecs)
    # one layer's slab of each pool leaf, on one device
    slabs = [sh.shard_shape(leaf.shape)[1:]
             for leaf, sh in zip(jax.tree.leaves(cache_shape),
                                 jax.tree.leaves(cshard))]
    return _counted(jax.jit(
        paged_decode_step,
        in_shardings=(shr.named(mesh, pspecs),
                      NamedSharding(mesh, P(bspec)),
                      cshard,
                      NamedSharding(mesh, P(bspec, None)),
                      NamedSharding(mesh, P(bspec))),
        out_shardings=(NamedSharding(mesh, logit_spec), cshard),
        donate_argnums=(2,),
    ), "serve.pool_move_bytes",
        functools.partial(pool_move_bytes, slabs=slabs))


# --------------------------------------------------------------------------
# dry-run input specs (ShapeDtypeStruct stand-ins, zero allocation)
# --------------------------------------------------------------------------


def input_specs(cfg: ModelConfig, shape: ShapeConfig, model: Model,
                kind: Optional[str] = None) -> Dict[str, Any]:
    """Stand-ins for every model input of one (arch x shape) cell.

    train  : packed batch {"inputs","labels","weights"}
    prefill: {"inputs"}
    decode : {"tokens", "cache", "pos"} — one new token against a
             seq_len-deep cache (the assigned decode_* semantics).
    """
    kind = kind or shape.kind
    b, s = shape.global_batch, shape.seq_len
    f32, i32 = jnp.float32, jnp.int32
    stub = cfg.frontend != "token"
    if kind == "train":
        inp = (jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16)
               if stub else jax.ShapeDtypeStruct((b, s), i32))
        return {"inputs": inp,
                "labels": jax.ShapeDtypeStruct((b, s), i32),
                "weights": jax.ShapeDtypeStruct((b, s), f32)}
    if kind == "prefill":
        inp = (jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16)
               if stub else jax.ShapeDtypeStruct((b, s), i32))
        return {"inputs": inp}
    if kind == "decode":
        cache = jax.eval_shape(functools.partial(model.init_cache, b, s))
        tok = (jax.ShapeDtypeStruct((b, cfg.d_model), jnp.bfloat16)
               if stub else jax.ShapeDtypeStruct((b,), i32))
        return {"tokens": tok, "cache": cache,
                "pos": jax.ShapeDtypeStruct((), i32)}
    raise ValueError(kind)
