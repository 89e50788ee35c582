"""Step builders: jitted train / prefill / decode with explicit shardings.

Training has one skeleton. ``train_schedule`` decides, once for each
(model, config, mesh), which schedule the cell runs and what follows
from it: the reduction axes, hierarchy and error feedback, the bucket
layout, the stage plan and the state layout. The state functions
(``state_shapes``, ``state_specs``, ``init_train_state``,
``checkpoint_format``) and the step builders read that record.
``build_train_step`` validates, decides, builds the schedule's step and
hands it to the one jit wrapper, ``_jit_train_step``. Every schedule
takes the packed (dummy-padded) global batch with per-token weights,
which make heterogeneous capacity exact (core M1/M3):

  * ``allreduce`` — XLA reduces the grads from the shardings (FSDP =>
    reduce-scatter + all-gather); accumulation (core/accumulate.py);
    tree AdamW / LAMB, its state sharded like the params (ZeRO-1);
  * ``bucketed_allreduce`` — grads packed into fixed-size f32 buckets
    (core/buckets.py), ONE psum_scatter + ONE all_gather over the DP
    axes;
  * ``hierarchical`` — params replicated over "pod", FSDP over "data";
    the cross-pod leg an explicit shard_map(axis_names={"pod"})
    collective, optionally int8 with error feedback: the bucketed
    engine with ``bucket_mb > 0`` (one flat error array), the legacy
    per-leaf walk with ``bucket_mb == 0`` (a per-leaf error mirror);
  * ``overlap_buckets`` — after the backward, the double-buffered
    per-bucket exchange with each bucket's flat-view optimizer update
    fused in as it lands; the moments packed (num_buckets,
    bucket_elems), replicated over the reduction axes;
  * ``overlap_backward`` — the same packed state, the buckets flushed
    DURING a staged layer-by-layer backward (LAMB streams there too);
  * ``pipeline`` — contiguous capacity-sized layer stages
    (core/pipeline.py), the accumulation microbatches streamed through
    them in 1F1B (or GPipe) order; grads reduced by XLA, or per stage
    through the bucket engine with ``bucketed_allreduce``;
  * ``canonical`` — per-row grads summed with one fixed reduction tree,
    bit-identical across capacity replans.

The overlap schedules share the per-bucket AdamW hook and the
packed-moment result; ``overlap_backward`` and the bucketed pipeline
share the staged plumbing (leaf pieces, stream-buffer scatter,
rank-major microbatch split, embedding VJP, flush legs).

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
# the schedule decision and the train state
# --------------------------------------------------------------------------


class TrainState(NamedTuple):
    params: Any
    opt: adam.AdamState
    err: Any                       # error-feedback state or () when unused
    # layouts: TrainSchedule.packed (opt.m / opt.v) and .err (err)


SCHEDULES = ("allreduce", "bucketed_allreduce", "hierarchical",
             "overlap_buckets", "overlap_backward", "pipeline",
             "canonical")


class TrainSchedule(NamedTuple):
    """What ``train_schedule`` decided for one (model, config, mesh)."""
    kind: str                      # one of SCHEDULES
    hier: bool                     # hierarchical, on a mesh with "pod"
    # hier or bucketed_allreduce: the gradient exchange is ours, over
    # `axes`, and the params are replicated across those axes
    explicit: bool
    axes: Tuple[str, ...]          # the explicit exchange's manual axes
    ranks: int                     # ranks of that exchange
    inner_dp: int                  # DP ranks inside one of those ranks
    compress: str                  # the exchange's wire format
    err: Optional[str]             # error state: "flat" | "per_leaf" | None
    packed: bool                   # opt.m / opt.v packed per bucket
    # the overlap schedules stream the optimizer per bucket; global-norm
    # clipping needs every bucket first, so it updates behind a barrier
    stream: bool
    layout: Optional[bkt.BucketLayout]
    splan: Optional[pipe.StagePlan]
    params_shape: Any

    @property
    def axis(self):
        """The exchange's axis name(s), as a collective takes them."""
        return self.axes[0] if len(self.axes) == 1 else self.axes


def train_schedule(model: Model, tcfg: TrainConfig,
                   mesh: Mesh) -> TrainSchedule:
    """Decide which train schedule this cell runs, and what follows.

    The one place the decision is made. Two fallbacks are silent by
    design: ``hierarchical`` on a mesh without a "pod" axis runs the
    plain ``allreduce`` schedule, and ``overlap`` on a mesh with no
    reduction axes runs its reduction's schedule without overlap.
    Mesh-independent rules are checked here (``HetConfig.validate``);
    ``validate_train_config`` adds the model and mesh rules.
    """
    het = tcfg.het.validate()
    hier = het.grad_reduction == "hierarchical" \
        and "pod" in mesh.axis_names
    bucketed = het.grad_reduction == "bucketed_allreduce"
    axes = ("pod",) if hier else (mesh_dp_axes(mesh) if bucketed else ())
    ranks = math.prod(mesh.shape[a] for a in axes)
    params_shape = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    layout = None
    if axes and het.bucket_mb > 0:
        # buckets divide into per-rank shards of whole quantization
        # blocks (ranks * _BLOCK)
        layout = bkt.build_layout(params_shape, bucket_mb=het.bucket_mb,
                                  multiple_of=ranks * _BLOCK)
    if het.weighting == "canonical":
        kind = "canonical"
    elif het.pipeline_stages > 1:
        kind = "pipeline"
    elif het.overlap != "none" and axes:
        kind = "overlap_" + het.overlap
    elif hier:
        kind = "hierarchical"
    elif bucketed:
        kind = "bucketed_allreduce"
    else:
        kind = "allreduce"
    packed = kind.startswith("overlap_")
    err = None
    if hier and het.compression != "none" and het.error_feedback:
        err = "flat" if layout is not None else "per_leaf"
    return TrainSchedule(
        kind=kind, hier=hier, explicit=hier or bucketed, axes=axes,
        ranks=ranks, inner_dp=dp_size(mesh) // ranks,
        compress=het.compression if hier else "none", err=err,
        packed=packed, stream=packed and tcfg.optimizer.grad_clip <= 0,
        layout=layout, splan=stage_plan_for(model, tcfg),
        params_shape=params_shape)


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
    # the staged schedules run per-layer VJP segments over the uniform
    # block stack. Checked whatever the mesh: overlap on a mesh with no
    # reduction axes falls back to the plain schedule, and an unsupported
    # model must not ride that fallback silently and then fail the
    # moment the same config meets a real mesh (tests/test_overlap.py)
    for staged, on, hint in (
            ("overlap='backward'", het.overlap == "backward",
             " — use overlap='buckets'"),
            ("pipeline_stages > 1", het.pipeline_stages > 1, "")):
        if on and not tr.supports_staged_backward(model.cfg):
            raise ValueError(
                f"HetConfig.{staged} stages the backward over the uniform "
                f"block stack (dense | moe | mla); stack plan "
                f"'{tr.stack_plan(model.cfg)}' of '{model.cfg.name}' is "
                f"not supported{hint}")
        if on and model.cfg.scan_layers:
            raise ValueError(
                f"HetConfig.{staged} needs ModelConfig.scan_layers=False: "
                "its per-layer VJP segments are an unrolled program, and "
                "bit-exactness with the monolithic path requires the "
                "monolithic stack unrolled too (launch/train.py: "
                "--no-scan-layers)")
    if het.pipeline_stages > 1:
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
    """The gradient bucket grid of this cell's explicit exchange (None
    when the cell has none)."""
    return train_schedule(model, tcfg, mesh).layout


def checkpoint_format(model: Model, tcfg: TrainConfig, mesh: Mesh) -> Dict:
    """The checkpoint ``"format"`` meta block for this config cell.

    Records how this cell lays TrainState out on disk: which fields are
    saved packed (the overlap schedules store the optimizer moments as
    one (num_buckets, bucket_elems) stack) and the versioned
    ``BucketLayout`` record + fingerprint describing that grid, so a
    restore into ANY other cell can translate through the flat stream
    (checkpoint/repack.py) instead of failing on shape mismatch.
    ``hosts`` is the v3 per-host shard count (one writer per pod — the
    fleet unit that owns its own disk); the layout record carries the
    matching bucket-row extents each host writes.
    """
    from repro.checkpoint import repack

    sched = train_schedule(model, tcfg, mesh)
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
    if sched.splan is not None:
        fmt["pipeline"] = pipe.stage_record(sched.splan)
    if sched.packed:
        paths = [repack.path_key(p) for p, _ in
                 jax.tree_util.tree_flatten_with_path(
                     sched.params_shape)[0]]
        rec = bkt.layout_record(sched.layout, leaf_paths=paths,
                                hosts=hosts)
        fmt.update(state="packed",
                   packed_fields=["opt/m", "opt/v"],
                   layout=rec,
                   fingerprint=rec["fingerprint"])
    return fmt


def state_shapes(model: Model, tcfg: TrainConfig, mesh: Mesh):
    return _state_shapes(tcfg, mesh, train_schedule(model, tcfg, mesh))


def _init_opt(params, tcfg: TrainConfig, sched: TrainSchedule):
    """The step-0 optimizer state: moments packed per bucket, or a
    pytree like the params."""
    if sched.packed:
        # the layout depends on the mesh's reduction ranks: re-meshing a
        # packed checkpoint goes through checkpoint/repack.py
        lo = sched.layout
        return adam.init_state_flat(lo.num_buckets, lo.bucket_elems,
                                    tcfg.optimizer)
    return adam.init_state(params, tcfg.optimizer)


def _state_shapes(tcfg: TrainConfig, mesh: Mesh,
                  sched: TrainSchedule) -> TrainState:
    params_shape = sched.params_shape
    opt_shape = jax.eval_shape(
        lambda p: _init_opt(p, tcfg, sched), params_shape)
    if sched.err == "flat":
        err_shape: Any = jax.ShapeDtypeStruct(
            sched.layout.error_shape(mesh.shape["pod"]), jnp.float32)
    elif sched.err == "per_leaf":
        err_shape = jax.tree.map(
            lambda p: jax.ShapeDtypeStruct((mesh.shape["pod"],) + p.shape,
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
    return _state_specs(model, mesh, train_schedule(model, tcfg, mesh))


def _state_specs(model: Model, mesh: Mesh,
                 sched: TrainSchedule) -> TrainState:
    pspecs = shr.param_specs(model.cfg, sched.params_shape, mesh)
    if sched.explicit:
        # params replicated across the manual reduction axes so the
        # gradient leg is ours to schedule (hierarchical keeps FSDP over
        # "data"; bucketed_allreduce replicates over the whole DP set)
        pspecs = jax.tree.map(lambda s: _strip_axes(s, sched.axes), pspecs,
                              is_leaf=lambda x: isinstance(x, P))
        # token-embedding gathers with a sharded vocab dim hit an XLA
        # SPMD-partitioner bug inside partially-manual regions; shard the
        # table on d_model only (gather pass-through dim) in this mode
        if isinstance(pspecs, dict) and "embed" in pspecs:
            tp = "model" if "model" in mesh.axis_names else None
            vshape = sched.params_shape["embed"].shape
            pspecs = dict(pspecs)
            pspecs["embed"] = shr.fit_spec(vshape, P(None, tp), mesh)
    if sched.packed:
        # packed moments: replicated over the reduction axes (the flat
        # stack mixes every leaf's sharding — the ZeRO-1 mirror does
        # not apply; documented trade in ROADMAP.md)
        ospecs = adam.AdamState(step=P(), m=P(), v=P())
    else:
        ospecs = adam.AdamState(step=P(), m=pspecs, v=pspecs)
    if sched.err == "flat":
        especs: Any = P("pod")
    elif sched.err == "per_leaf":
        especs = jax.tree.map(lambda s: P("pod", *s), pspecs,
                              is_leaf=lambda x: isinstance(x, P))
    else:
        especs = ()
    return TrainState(params=pspecs, opt=ospecs, err=especs)


def init_train_state(model: Model, tcfg: TrainConfig, mesh: Mesh,
                     key) -> TrainState:
    """Initialize on-device with the right shardings (M8: same init
    everywhere — a single global RNG key IS the broadcast)."""
    sched = train_schedule(model, tcfg, mesh)
    specs = _state_specs(model, mesh, sched)
    shapes = _state_shapes(tcfg, mesh, sched)

    def init(k):
        params = model.init_params(k)
        opt = _init_opt(params, tcfg, sched)
        err = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                           shapes.err)
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
# gradient reduction and optimizer pieces
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
    error state, or None when error feedback is off. The overlap
    schedules do NOT go through here — their fused reduce+optimizer
    pipelines never materialize the unpacked gradient tree.
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


def _carry_dtype(p):
    """Gradient-accumulation carry dtype: fp32, except when params are
    stored bf16 (arctic/deepseek giants), where an fp32 carry alone
    would blow the 16 GB budget — a bf16 carry there."""
    return p.dtype if p.dtype == jnp.bfloat16 else jnp.float32


def _tree_update(params, grads, opt, ocfg: OptimizerConfig, lr):
    """The tree-form optimizer step, LAMB or AdamW as ``ocfg`` names:
    (params', opt', metrics)."""
    apply = lamb.apply_update if ocfg.name == "lamb" else adam.apply_update
    return apply(params, grads, opt, ocfg, lr)


def _flat_views(layout: bkt.BucketLayout, ocfg: OptimizerConfig):
    """The packed layout's weight-decay mask, LAMB's per-element leaf
    ids (None for AdamW) and its leaf count."""
    dmask = bkt.decay_mask(layout)
    segs = bkt.segment_ids(layout) if ocfg.name == "lamb" else None
    return dmask, segs, len(layout.sizes)


def _flat_barrier_update(pb, red, m, v, lr_step, ocfg, lr, *, inv_w,
                         dmask, segs, n_leaves):
    """Whole-stack flat optimizer update behind the barrier.

    Shared by both overlap schedules for configs whose statistics need
    every reduced bucket BEFORE the first moment update (global-norm
    clipping), and by ``overlap_buckets`` for ALL of LAMB
    (``overlap_backward`` streams LAMB instead — optim/lamb.py has the
    full exactness rationale). Returns
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


def _adamw_bucket(ssq, g_k, rows, lr_step, ocfg, lr):
    """The per-bucket AdamW hook both overlap schedules stream: bucket
    k's scaled reduced gradient ``g_k`` applied to its flat ``rows``
    (params, m, v, decay mask) the moment it lands. ``ssq`` carries the
    squared gradient norm; returns (ssq', (params', m', v'))."""
    p_k, m_k, v_k, dm_k = rows
    out = adam.apply_update_flat(p_k, g_k, m_k, v_k, lr_step, ocfg, lr,
                                 decay_mask=dm_k)
    return ssq + jnp.sum(g_k * g_k), out


def _packed_result(params, m, v, err, o, w, gnorm, trust, lr_step, lr,
                   ocfg: OptimizerConfig):
    """The overlap schedules' (TrainState, metrics): packed moments and
    the streamed or barrier gradient norm and trust ratio."""
    loss = weighting.finalize(o, w)
    metrics = {"loss": loss, "weight": w, "grad_norm": gnorm, "lr": lr}
    if ocfg.name == "lamb":
        metrics["trust_ratio"] = trust
    new_state = TrainState(
        params=params, opt=adam.AdamState(step=lr_step, m=m, v=v),
        err=err)
    return new_state, metrics


# --------------------------------------------------------------------------
# monolithic schedules: allreduce, bucketed_allreduce, hierarchical,
# overlap_buckets, canonical
# --------------------------------------------------------------------------


def _inner_ctx(mesh: Mesh, sched: TrainSchedule) -> ParallelCtx:
    """The model's ParallelCtx inside one rank of the explicit exchange:
    the manual axes must not appear in sharding constraints there —
    hierarchical keeps "data" automatic inside the pod region,
    bucketed_allreduce makes the whole DP set manual."""
    if not sched.explicit:
        return make_parallel_ctx(mesh)
    return ParallelCtx(mesh=mesh, dp_axes=("data",) if sched.hier else (),
                       tp_axis=tp_axis(mesh))


def _grads_fn(model: Model, tcfg: TrainConfig, mesh: Mesh,
              sched: TrainSchedule):
    """``compute_grads(params, batch) -> (grad_of_sums, obj_sum,
    weight_sum)``, unscaled, over the rows of one rank of the schedule's
    exchange, with gradient accumulation."""
    inner_ctx = _inner_ctx(mesh, sched)
    accum = max(1, tcfg.het.accum_steps)
    pspecs = _state_specs(model, mesh, sched).params if sched.hier else None

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

    def compute_grads(params, batch):
        if accum == 1:
            (o, w), g = grad_fn(params, batch)
        else:
            mbs = acc.split_microbatches(batch, accum,
                                         num_ranks=sched.inner_dp)
            # unrolled-program class (scan_layers=False, required by
            # overlap="backward"): keep the accumulation unrolled too
            # so the staged backward stays bit-identical at accum > 1
            accumulate = (acc.scan_accumulate if model.cfg.scan_layers
                          else acc.unrolled_accumulate)
            g, o, w = accumulate(grad_fn, params, mbs,
                                 carry_dtype=_carry_dtype)
        if sched.hier:
            # inside the partially-manual region XLA's sharding
            # propagation can lose the (data, model) layout of the
            # gradients; re-pin them to the param specs so the pod
            # exchange moves shards, not replicated leaves
            g = jax.tree.map(jax.lax.with_sharding_constraint, g, pspecs)
        return g, o, w

    return compute_grads


def _build_reduced_step(model: Model, tcfg: TrainConfig, mesh: Mesh,
                        sched: TrainSchedule):
    """``allreduce``, ``bucketed_allreduce`` and ``hierarchical``: the
    whole gradient, reduced, then one tree-form optimizer step."""
    ocfg = tcfg.optimizer
    compute_grads = _grads_fn(model, tcfg, mesh, sched)
    layout, use_err = sched.layout, sched.err is not None
    q_impl = tcfg.het.quantize_impl

    if sched.hier:
        err_spec = P("pod") if use_err else P()

        def pod_local(params, b, err):
            g, o, w = compute_grads(params, b)
            with jax.named_scope("exchange"):
                # the cross-pod leg: bucketed engine or legacy walk
                if layout is not None:
                    g, ne = _reduce_bucketed(
                        g, err if use_err else None, axis="pod",
                        axis_size=sched.ranks, compress=sched.compress,
                        layout=layout, impl=q_impl)
                    ne = ne if ne is not None else ()
                else:
                    g, ne = _cross_pod_reduce(g, err, sched.compress,
                                              sched.ranks)
                return g, jax.lax.psum(o, "pod"), \
                    jax.lax.psum(w, "pod"), ne

        def reduced(state, batch):
            return jax.shard_map(
                pod_local, mesh=mesh,
                in_specs=(P(), P("pod"), err_spec),
                out_specs=(P(), P(), P(), err_spec),
                axis_names={"pod"}, check_vma=False,
            )(state.params, batch, state.err)
    elif sched.explicit:
        dp = sched.axes

        def dp_local(params, b):
            g, o, w = compute_grads(params, b)
            with jax.named_scope("exchange"):
                g, _ = _reduce_bucketed(g, None, axis=sched.axis,
                                        axis_size=sched.ranks,
                                        compress="none", layout=layout,
                                        impl=q_impl)
                return g, jax.lax.psum(o, dp), jax.lax.psum(w, dp)

        def reduced(state, batch):
            grads, o, w = jax.shard_map(
                dp_local, mesh=mesh,
                in_specs=(P(), P(dp)),
                out_specs=(P(), P(), P()),
                axis_names=set(dp), check_vma=False,
            )(state.params, batch)
            return grads, o, w, state.err
    else:
        def reduced(state, batch):
            grads, o, w = compute_grads(state.params, batch)
            return grads, o, w, state.err

    def train_step(state: TrainState, batch: Dict
                   ) -> Tuple[TrainState, Dict]:
        grads, o, w, new_err = reduced(state, batch)
        with jax.named_scope("exchange"):
            # the weighted mean over every rank's rows (with plain
            # allreduce the partitioner places the sums' collectives)
            loss = weighting.finalize(o, w)
            grads = weighting.scale_grads(grads, w)
        with jax.named_scope("optimizer"):
            lr = schedules.learning_rate(ocfg, state.opt.step + 1)
            params, opt, met = _tree_update(state.params, grads,
                                            state.opt, ocfg, lr)
        metrics = {"loss": loss, "weight": w, **met}
        return TrainState(params=params, opt=opt, err=new_err), metrics

    return train_step


def _build_overlap_buckets_step(model: Model, tcfg: TrainConfig,
                                mesh: Mesh, sched: TrainSchedule):
    """``overlap_buckets``: the optimizer moves INSIDE the manual region.

    The per-bucket pipeline exchanges bucket k while bucket k+1
    quantizes, and the flat-view AdamW update for bucket k runs the
    moment it lands. The packed moments enter/leave the region
    replicated over the reduction axes; every rank computes the
    identical update.
    """
    ocfg = tcfg.optimizer
    compute_grads = _grads_fn(model, tcfg, mesh, sched)
    layout, use_err = sched.layout, sched.err is not None
    dmask, segs, n_leaves = _flat_views(layout, ocfg)

    def local(params, b, err, m, v, lr_step, lr):
        """One rank of the reduction: its grads, reduced bucket by bucket
        with each bucket's update fused, on its (nb, be) error slice."""
        g, o, w = compute_grads(params, b)
        o = jax.lax.psum(o, sched.axis)
        w = jax.lax.psum(w, sched.axis)
        e = (err.reshape(layout.num_buckets, layout.bucket_elems)
             if use_err else None)
        gb = bkt.pack_buckets(g, layout)
        pb = bkt.pack_buckets(params, layout)
        inv_w = 1.0 / jnp.maximum(w, 1e-9)
        kwargs = dict(axis=sched.axis, axis_size=sched.ranks,
                      compress=(sched.compress != "none"),
                      block_size=_BLOCK, impl=tcfg.het.quantize_impl)
        if sched.stream and ocfg.name != "lamb":
            outs, new_e, ssq = bkt.exchange_buckets_overlapped(
                gb, e, fn_carry=jnp.zeros((), jnp.float32),
                bucket_fn=lambda ssq, red_k, xs_k, k: _adamw_bucket(
                    ssq, red_k * inv_w, xs_k, lr_step, ocfg, lr),
                bucket_xs=(pb, m, v, dmask), **kwargs)
            new_pb, new_m, new_v = outs
            gnorm = jnp.sqrt(ssq)
            trust = jnp.ones((), jnp.float32)
        else:
            # clip barrier, and ALL of LAMB in this after-backward
            # engine: fusing LAMB's hook into the per-bucket scan
            # deterministically perturbs how XLA compiles the
            # whole-module gradient/reduction program (~0.4% of
            # reduced-grad elements move 1 ulp, measured across every
            # hook/optimization_barrier variant), which breaks the
            # backward==buckets bitwise contract (tests/test_overlap.py).
            # overlap_backward streams LAMB bitwise-safely; here the
            # barrier form is the bit-exact choice — and the exchange is
            # already fully overlapped bucket-to-bucket, so only the
            # optimizer pass trails.
            red, new_e, _ = bkt.exchange_buckets_overlapped(
                gb, e, **kwargs)
            new_pb, new_m, new_v, gnorm, trust = _flat_barrier_update(
                pb, red, m, v, lr_step, ocfg, lr, inv_w=inv_w,
                dmask=dmask, segs=segs, n_leaves=n_leaves)
        new_params = bkt.unpack_buckets(new_pb, layout)
        if use_err:
            err = new_e.reshape(1, layout.num_buckets, layout.bucket_elems)
        return new_params, new_m, new_v, err, o, w, gnorm, trust

    def train_step(state: TrainState, batch: Dict
                   ) -> Tuple[TrainState, Dict]:
        lr_step = state.opt.step + 1
        lr = schedules.learning_rate(ocfg, lr_step)
        err_spec = P("pod") if use_err else P()
        batch_spec = P("pod") if sched.hier else P(sched.axes)
        (new_params, new_m, new_v, new_err, o, w, gnorm,
         trust) = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(), batch_spec, err_spec, P(), P(), P(), P()),
            out_specs=(P(), P(), P(), err_spec, P(), P(), P(), P()),
            axis_names=set(sched.axes), check_vma=False,
        )(state.params, batch, state.err if use_err else (), state.opt.m,
          state.opt.v, lr_step, lr)
        return _packed_result(
            new_params, new_m, new_v, new_err if use_err else state.err,
            o, w, gnorm, trust, lr_step, lr, ocfg)

    return train_step


def _build_canonical_step(model: Model, tcfg: TrainConfig, mesh: Mesh,
                          sched: TrainSchedule):
    """``canonical``: the order-canonical executor (core/weighting.py).

    Per-row vmapped grads summed along the global-row axis with ONE
    fixed reduction tree. The row->rank partition drops out of the
    float math entirely, so two runs consuming the same global rows are
    bit-identical whatever capacity replans happened in between —
    provided the sampler emits rows in canonical global order
    (HetSampler(canonical_order=True)).
    """
    ocfg = tcfg.optimizer

    def row_loss(p, b):
        return model.loss_fn(p, b, label_smoothing=tcfg.label_smoothing)

    def train_step(state: TrainState, batch: Dict
                   ) -> Tuple[TrainState, Dict]:
        (o_r, w_r), g_r = weighting.per_row_values(
            row_loss, state.params, batch)
        loss, grads, _, w = weighting.canonical_aggregate(o_r, w_r, g_r)
        lr = schedules.learning_rate(ocfg, state.opt.step + 1)
        params, opt, met = _tree_update(state.params, grads, state.opt,
                                        ocfg, lr)
        metrics = {"loss": loss, "weight": w, **met}
        return TrainState(params=params, opt=opt, err=state.err), metrics

    return train_step


# --------------------------------------------------------------------------
# staged plumbing: overlap_backward and the bucketed pipeline
# --------------------------------------------------------------------------


def _path_top(entry) -> str:
    """Top-level key of a tree_flatten_with_path path entry."""
    for attr in ("key", "name", "idx"):
        if hasattr(entry, attr):
            return str(getattr(entry, attr))
    return str(entry)


def _leaf_pieces(params_shape: Any, cfg: ModelConfig,
                 layer_stage: Callable[[int], int]):
    """Per-leaf ``(offset_within_leaf, n, stage)`` pieces: the model's
    layer partition mapped onto the flat gradient stream, for
    ``core/buckets.py::bucket_readiness``.

    Stacked ``layers`` leaves split into per-layer slices, layer *l*
    landing at ``layer_stage(l)``; the head leaves land at stage 0 and
    the embedding table one stage after layer 0, last (a tied table
    also receives a head-stage contribution, so its grad is only final
    at the end).
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
            pieces.append([(l * per, per, layer_stage(l))
                           for l in range(L)])
        elif top == "embed":
            pieces.append([(0, n, layer_stage(0) + 1)])
        elif top in head_keys:
            pieces.append([(0, n, 0)])
        else:
            raise ValueError(
                f"staged train step: unexpected param subtree '{top}' "
                f"(uniform stack expects embed / final_norm / lm_head / "
                f"layers)")
    return pieces


def _stream_scatter(sched: TrainSchedule, num_layers: int):
    """``scatter(buf, top, grads, layers=None)``: scatter-add a landed
    grad subtree into the (ranks, padded_total) f32 stream buffer;
    ``layers=(r0, r1)`` places a stacked subtree holding layers r0..r1
    (exclusive) within each of its leaves."""
    slots: Dict[str, list] = {}
    for (path, _), off, size in zip(
            jax.tree_util.tree_flatten_with_path(sched.params_shape)[0],
            sched.layout.offsets, sched.layout.sizes):
        slots.setdefault(_path_top(path[0]), []).append((off, size))

    def scatter(buf, top, grads, layers=None):
        leaves = jax.tree.leaves(grads)
        # zero-leaf subtrees (non-parametric norms) never reach the
        # stream
        got = slots.get(top, [])
        assert len(leaves) == len(got), (top, len(leaves), len(got))
        for g, (off, size) in zip(leaves, got):
            if layers is not None:
                r0, r1 = layers
                per = size // num_layers
                off, size = off + r0 * per, (r1 - r0) * per
            buf = buf.at[:, off:off + size].add(
                g.reshape(sched.ranks, size).astype(jnp.float32))
        return buf

    return scatter


def _rank_spec(sched: TrainSchedule) -> P:
    """The spec of a rank-major (ranks, rows per rank, ...) array."""
    return P("pod", "data") if sched.hier else P(sched.axes)


def _rank_microbatches(batch: Dict, sched: TrainSchedule, accum: int):
    """The batch reshaped rank-major and cut into ``accum``
    microbatches with the row assignment of the monolithic
    ``acc.split_microbatches`` (inner-rank-major, so every microbatch
    takes an equal slice of every inner DP rank's buffer)."""
    ranks, inner = sched.ranks, sched.inner_dp
    sb = jax.tree.map(
        lambda v: jax.lax.with_sharding_constraint(
            v.reshape(ranks, v.shape[0] // ranks, *v.shape[1:]),
            _rank_spec(sched)), batch)
    if accum == 1:
        return [sb]

    def split(a):
        b, tail = a.shape[1], a.shape[2:]
        if b % (inner * accum):
            raise ValueError(
                f"rows {b} per reduction rank not divisible by "
                f"accum {accum} x inner ranks {inner}")
        if inner > 1:
            a = jnp.swapaxes(a.reshape(ranks, inner, accum,
                                       b // inner // accum, *tail), 1, 2)
        return a.reshape(ranks, accum, b // accum, *tail)

    s = {k: split(v) for k, v in sb.items()}
    return [jax.tree.map(lambda a: a[:, i], s) for i in range(accum)]


def _embed_grads(embed_fn, emb_p, inputs, x_cot):
    """The token table's gradient from its output's cotangent, per
    reduction rank."""
    def embed_stage(ep, i, xc):
        _, vjp = jax.vjp(lambda q: embed_fn(q, i), ep)
        return vjp(xc)[0]

    return jax.vmap(embed_stage, in_axes=(None, 0, 0))(
        emb_p, inputs, x_cot)["embed"]


def _flush_pipeline(mesh: Mesh, sched: TrainSchedule, readiness,
                    q_impl: str, err_in=None, **hook):
    """``(pipeline, flush)``: a ``core/buckets.py::BucketFlushPipeline``
    over the stream buffer, and ``flush(stage, buf)``, which feeds it
    every bucket that is ready at ``stage``.

    Bucket k's send-side leg quantizes/packs per rank at the top level
    (no collectives — it overlaps the previous bucket's in-flight
    exchange); its link and receive legs run in a small manual region
    of their own — the only collectives in the program, so they
    interleave with the staged backward in program order. ``err_in`` is
    the flat (ranks, num_buckets, bucket_elems) error state, or None;
    ``hook`` the pipeline's ``bucket_fn`` and ``fn_carry``.
    """
    ranks, red_axis = sched.ranks, sched.axis
    shard = sched.layout.bucket_elems // ranks
    compress = sched.compress != "none"
    use_err = err_in is not None
    axis_set = set(sched.axes)
    buf_spec = P(red_axis)

    def prep(k, raw_k):
        x_k = raw_k.reshape(ranks, ranks, shard)
        if not compress:
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
        payload, resid1 = prepared
        if compress and use_err:
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
                compress=compress, block_size=_BLOCK,
                impl=q_impl, interpret=False, onehot=onehot)
            return red

        red = jax.shard_map(
            region, mesh=mesh, in_specs=buf_spec, out_specs=P(),
            axis_names=axis_set, check_vma=False)(payload)
        return red, None

    pipeline = bkt.BucketFlushPipeline(readiness, prep, exchange, **hook)
    be = sched.layout.bucket_elems

    def flush(stage, buf):
        pipeline.flush_ready_buckets(
            stage, lambda k: buf[:, k * be:(k + 1) * be])

    return pipeline, flush


def _stream_buffer(sched: TrainSchedule):
    """The zeroed (ranks, padded_total) f32 gradient stream, one row per
    reduction rank."""
    return jax.lax.with_sharding_constraint(
        jnp.zeros((sched.ranks, sched.layout.padded_total), jnp.float32),
        P(sched.axis))


def _build_backward_overlap_step(model: Model, tcfg: TrainConfig,
                                 mesh: Mesh, sched: TrainSchedule):
    """``overlap_backward``: flush gradient buckets DURING backprop
    instead of after it.

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
    runtime the overlap.

    Exactness: fp32 with ``grad_clip=0`` is bit-identical to the
    monolithic path (same config, ``overlap="none"``) — per-bucket
    exchanges match the monolithic exchange slice-for-slice and the
    flat AdamW stream matches the tree update (tests/test_overlap.py).
    LAMB streams its moment updates and norm partials per bucket with
    one trailing trust pass (optim/lamb.py — bitwise-equal to the
    barrier form by construction); global-norm clip keeps the
    in-backward pipelined exchange but applies the flat update behind
    a barrier. Gradient accumulation stages every microbatch's backward
    and flushes only during the last one (the bucket is final only
    then); the accumulator is the fp32 stream buffer, so bf16-carry
    configs differ from the monolithic bf16 carry by that last rounding
    step (documented trade).
    """
    from repro.models import transformer as tr

    cfg = model.cfg
    ocfg = tcfg.optimizer
    accum = max(1, tcfg.het.accum_steps)
    L = cfg.num_layers
    layout, use_err = sched.layout, sched.err is not None
    dmask, segs, n_leaves = _flat_views(layout, ocfg)
    readiness = bkt.bucket_readiness(layout, _leaf_pieces(
        sched.params_shape, cfg, lambda l: L - l))
    scatter = _stream_scatter(sched, L)
    token_frontend = cfg.frontend == "token"
    seg = tr.staged_uniform_segments(
        cfg, _inner_ctx(mesh, sched), label_smoothing=tcfg.label_smoothing)
    embed_fn, layer_fn = seg["embed_fn"], seg["layer_fn"]
    head_fn, head_keys = seg["head_fn"], seg["head_keys"]

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
            buf = scatter(buf, key, g_hp[key])
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
            buf = scatter(buf, "layers", g_lp, layers=(l, l + 1))
            if flush is not None:
                flush(L - l, buf)
        if token_frontend:
            buf = scatter(buf, "embed", _embed_grads(
                embed_fn, emb_p, mb["inputs"], x_cot))
        if flush is not None:
            flush(L + 1, buf)
        return buf, o, w

    def train_step(state: TrainState, batch: Dict
                   ) -> Tuple[TrainState, Dict]:
        lr_step = state.opt.step + 1
        lr = schedules.learning_rate(ocfg, lr_step)
        params = state.params
        mbs = _rank_microbatches(batch, sched, accum)
        lps = [jax.tree.map(lambda a: a[l], params["layers"])
               for l in range(L)]
        pb = bkt.pack_buckets(params, layout)
        cell: Dict[str, Any] = {}
        hook: Dict[str, Any] = {}
        if sched.stream:
            if ocfg.name == "lamb":
                # stream moments + per-leaf norm partials per bucket;
                # the trust-scaled step itself trails (finish below)
                def bucket_fn(ssq, red_k, k):
                    g_k = red_k * cell["inv_w"]
                    pf, upd, mf, vf = adam.flat_adamw_terms(
                        pb[k], g_k, state.opt.m[k], state.opt.v[k],
                        lr_step, ocfg, decay_mask=dmask[k])
                    psq, usq = lamb.bucket_norm_terms(
                        pf, upd, segs[k], n_leaves)
                    return (ssq + jnp.sum(g_k * g_k),
                            (pf, upd, mf, vf, psq, usq))
            else:
                def bucket_fn(ssq, red_k, k):
                    return _adamw_bucket(
                        ssq, red_k * cell["inv_w"],
                        (pb[k], state.opt.m[k], state.opt.v[k],
                         dmask[k]), lr_step, ocfg, lr)

            hook = dict(bucket_fn=bucket_fn,
                        fn_carry=jnp.zeros((), jnp.float32))
        pipeline, flush = _flush_pipeline(
            mesh, sched, readiness, tcfg.het.quantize_impl,
            state.err if use_err else None, **hook)

        buf = _stream_buffer(sched)
        o_acc = jnp.zeros((sched.ranks,), jnp.float32)
        w_acc = jnp.zeros((sched.ranks,), jnp.float32)
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
        if sched.stream and ocfg.name == "lamb":
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
        elif sched.stream:
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
            new_err = jnp.stack(errs, axis=1).reshape(state.err.shape)
        return _packed_result(new_params, new_m, new_v, new_err, o, w,
                              gnorm, trust, lr_step, lr, ocfg)

    return train_step


# --------------------------------------------------------------------------
# pipeline-parallel schedule (HetConfig.pipeline_stages > 1)
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


def _build_pipeline_step(model: Model, tcfg: TrainConfig, mesh: Mesh,
                         sched: TrainSchedule):
    """``pipeline``: capacity-sized contiguous stages, the accumulation
    microbatches streamed through them in 1F1B (or GPipe) program
    order.

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

    Reduction: with ``grad_reduction="allreduce"`` (no bucket layout)
    XLA reduces from the shardings exactly as the monolithic path;
    with ``"bucketed_allreduce"`` the grads live in the flat (ranks,
    padded_total) stream and each stage's buckets flush through their
    own small exchange regions the moment the last microbatch's B event
    for that stage lands — per-stage reduction overlapping the
    remaining drain, with the staged plumbing of ``overlap_backward``.
    The tree-form optimizer runs after the drain (``overlap`` must be
    "none" with pipelining — HetConfig.validate), so moments stay a
    pytree and checkpoints restore bit-exactly across stage plans,
    including pure DP.

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
    splan = sched.splan
    S = splan.num_stages
    ranges = splan.stage_ranges()
    events = pipe.program_order(S, M, schedule=tcfg.het.pipeline_schedule)
    token_frontend = cfg.frontend == "token"
    seg = tr.pipeline_stage_fns(cfg, _inner_ctx(mesh, sched), ranges,
                                label_smoothing=tcfg.label_smoothing)
    embed_fn, head_fn = seg["embed_fn"], seg["head_fn"]
    head_keys, stage_fwd = seg["head_keys"], seg["stage_fwd"]

    if sched.layout is None:
        # ---- plain-SPMD path (grad_reduction="allreduce") ------------
        n_dp = dp_size(mesh)
        act_spec = shr.stage_activation_spec(
            mesh, tcfg.shape.global_batch // M)

        def train_step(state: TrainState, batch: Dict
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
                lambda p: jnp.zeros(p.shape, _carry_dtype(p)), params)
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
            new_params, opt, met = _tree_update(params, grads, state.opt,
                                                ocfg, lr)
            metrics = {"loss": loss, "weight": w_acc, **met}
            return TrainState(params=new_params, opt=opt,
                              err=state.err), metrics

        return train_step

    # ---- bucketed path (grad_reduction="bucketed_allreduce") ---------
    # rank-major vmapped stage VJPs with the flat f32 gradient stream;
    # per-stage bucket flushes through small manual exchange regions
    layout = sched.layout
    rank_spec = _rank_spec(sched)
    readiness = bkt.bucket_readiness(layout, _leaf_pieces(
        sched.params_shape, cfg, lambda l: S - 1 - splan.stage_of_layer(l)))
    scatter = _stream_scatter(sched, cfg.num_layers)

    def train_step(state: TrainState, batch: Dict
                   ) -> Tuple[TrainState, Dict]:
        lr_step = state.opt.step + 1
        lr = schedules.learning_rate(ocfg, lr_step)
        params = state.params
        mbs = _rank_microbatches(batch, sched, M)
        slices = [jax.tree.map(lambda a: a[r0:r1], params["layers"])
                  for (r0, r1) in ranges]
        emb_p = {"embed": params["embed"]} if token_frontend else {}
        hp = {k: params[k] for k in head_keys}
        pipeline_fl, flush = _flush_pipeline(mesh, sched, readiness,
                                             tcfg.het.quantize_impl)
        buf = _stream_buffer(sched)
        o_acc = jnp.zeros((sched.ranks,), jnp.float32)
        w_acc = jnp.zeros((sched.ranks,), jnp.float32)
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
                    xa = (x0, jnp.zeros((sched.ranks,), jnp.float32))
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
                        buf = scatter(buf, key, g_hp[key])
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
                buf = scatter(buf, "layers", g_sl, layers=ranges[s])
                if m == M - 1:
                    flush(S - 1 - s, buf)
                if s > 0:
                    cots[(s - 1, m)] = (
                        _pipe_send(x_cot, mesh, rank_spec, -1), a_cot)
                else:
                    if token_frontend:
                        g_emb = _embed_grads(embed_fn, emb_p,
                                             mb["inputs"], x_cot)
                        if m in head_emb:
                            g_emb = g_emb + head_emb.pop(m)
                        buf = scatter(buf, "embed", g_emb)
                    if m == M - 1:
                        flush(S, buf)
        outs, _, _ = pipeline_fl.finish()
        red = jnp.stack(outs)
        grads = bkt.unpack_buckets(red, layout)
        o, w = jnp.sum(o_acc), jnp.sum(w_acc)
        loss = weighting.finalize(o, w)
        grads = weighting.scale_grads(grads, w)
        new_params, opt, met = _tree_update(params, grads, state.opt,
                                            ocfg, lr)
        metrics = {"loss": loss, "weight": w, **met}
        return TrainState(params=new_params, opt=opt,
                          err=state.err), metrics

    return train_step


# --------------------------------------------------------------------------
# train step
# --------------------------------------------------------------------------


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


def _jit_train_step(train_step, model: Model, tcfg: TrainConfig,
                    mesh: Mesh, sched: TrainSchedule):
    """The one jit wrapper of every schedule: the state in and out with
    the schedule's specs and donated, the batch as ``launch/train.py``
    feeds it, compiled ahead and counted (``train.exchange_bytes``).

    Packed batches hold one capacity-plan buffer per DP rank
    (``buffer_rows * dp_size`` rows), so they always shard over the DP
    axes — even when the global batch does not divide the DP size, as
    uneven row shares make common. Canonical batches are the
    ``global_batch`` rows in global order.
    """
    specs = shr.named(mesh, _state_specs(model, mesh, sched))
    rows = (tcfg.shape.global_batch if sched.kind == "canonical"
            else dp_size(mesh))
    bspecs = shr.named(mesh, shr.batch_specs(model.cfg, mesh, rows))
    return _counted(jax.jit(
        train_step,
        in_shardings=(specs, bspecs),
        out_shardings=(specs, None),
        donate_argnums=(0,),
    ), "train.exchange_bytes", exchange_bytes)


def build_train_step(model: Model, tcfg: TrainConfig, mesh: Mesh
                     ) -> Callable[[TrainState, Dict], Tuple[TrainState,
                                                             Dict]]:
    validate_train_config(model, tcfg, mesh)
    sched = train_schedule(model, tcfg, mesh)
    build = {"overlap_buckets": _build_overlap_buckets_step,
             "overlap_backward": _build_backward_overlap_step,
             "pipeline": _build_pipeline_step,
             "canonical": _build_canonical_step,
             }.get(sched.kind, _build_reduced_step)
    return _jit_train_step(build(model, tcfg, mesh, sched), model, tcfg,
                           mesh, sched)


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
