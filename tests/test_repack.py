"""Checkpoint repack layer: layout-portable exact resume.

Fast tests cover the path-key escaping, the flat-stream translations
(packed <-> pytree <-> packed, bit-exact, Adam and LAMB state incl. the
flat error-feedback stack), structured meta serialization, crash
atomicity, and the consumed-row resume validation. The acceptance bar
— save under ``overlap="buckets"``, restore into a different layout /
a re-meshed pod count, and continue bit-identically — runs under a
multi-device mesh in a subprocess, per the project convention that only
children force device counts.
"""
import json
import logging
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import repack
from repro.checkpoint.checkpoint import (CheckpointCorruptError,
                                         CheckpointManager)
from repro.configs.base import OptimizerConfig
from repro.core import buckets as bkt
from repro.core import elastic
from repro.core.capacity import CapacityPlan, plan_capacities
from repro.launch.mesh import make_mesh
from repro.optim import adam

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_child(code: str, devices: int = 8, timeout: int = 1200) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=timeout)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return proc.stdout


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    ks = jax.random.split(k, 4)
    return {
        "w": jax.random.normal(ks[0], (37, 8), jnp.float32),
        "b": jax.random.normal(ks[1], (13,), jnp.float32),
        "deep": {"m": jax.random.normal(ks[2], (5, 3, 2), jnp.float32),
                 "s": jax.random.normal(ks[3], (101,), jnp.float32)},
    }


# --------------------------------------------------------------------------
# path keys
# --------------------------------------------------------------------------


def test_path_keys_escape_slashes_and_attr_keys():
    """Dict keys containing '/' cannot collide with nested paths, and
    NamedTuple fields map to bare names (not ``str(GetAttrKey)``)."""
    flat = repack.flatten_with_paths(
        {"a/b": {"c": np.ones(1)}, "a": {"b/c": np.zeros(1)}})
    assert sorted(flat) == ["a%2Fb/c", "a/b%2Fc"]

    st = adam.AdamState(step=np.int32(1), m={"w": np.ones(2)},
                        v={"w": np.ones(2)})
    keys = sorted(repack.flatten_with_paths({"opt": st}))
    assert keys == ["opt/m/w", "opt/step", "opt/v/w"]


def test_flatten_collision_raises_at_save_time(monkeypatch, tmp_path):
    """Exotic key types whose str() collides must fail the SAVE, not
    corrupt the checkpoint silently."""
    monkeypatch.setattr(repack, "path_component", lambda p: "same")
    with pytest.raises(ValueError, match="collision"):
        repack.flatten_with_paths({"a": np.ones(1), "b": np.ones(1)})
    mgr = CheckpointManager(str(tmp_path))
    with pytest.raises(ValueError, match="collision"):
        mgr.save(1, {"a": jnp.ones(1), "b": jnp.ones(1)}, block=True)
    assert mgr.all_steps() == []          # nothing committed


# --------------------------------------------------------------------------
# the flat stream
# --------------------------------------------------------------------------


def test_fit_stream_pads_trims_and_rejects_nonzero_tail():
    s = np.arange(1, 5, dtype=np.float32)
    np.testing.assert_array_equal(repack.fit_stream(s, 6),
                                  [1, 2, 3, 4, 0, 0])
    padded = np.concatenate([s, np.zeros(3, np.float32)])
    np.testing.assert_array_equal(repack.fit_stream(padded, 4), s)
    with pytest.raises(ValueError, match="nonzero data"):
        repack.fit_stream(s, 3)


def test_layout_record_roundtrip_and_fingerprint():
    tree = _tree()
    lo_a = bkt.build_layout(tree, bucket_mb=1e-4, multiple_of=8)
    lo_b = bkt.build_layout(tree, bucket_mb=3e-4, multiple_of=16)
    paths = [repack.path_key(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(tree)[0]]
    rec = bkt.layout_record(lo_a, leaf_paths=paths)
    back = bkt.layout_from_record(rec, treedef=lo_a.treedef)
    assert back.shapes == lo_a.shapes
    assert back.offsets == lo_a.offsets
    assert (back.num_buckets, back.bucket_elems) == (lo_a.num_buckets,
                                                     lo_a.bucket_elems)
    # the record survives a JSON round trip with a stable fingerprint
    import json
    rec2 = json.loads(json.dumps(rec))
    assert bkt.layout_fingerprint(rec2) == rec["fingerprint"]
    assert (bkt.layout_record(lo_b)["fingerprint"] != rec["fingerprint"])
    with pytest.raises(ValueError, match="newer"):
        bkt.layout_from_record({**rec, "version": 999})


# --------------------------------------------------------------------------
# repack round trips (satellite: bit-exact for Adam and LAMB, incl. the
# flat error-feedback state)
# --------------------------------------------------------------------------


class _State(adam.AdamState):
    pass


def _mk_state(params, opt, err=()):
    from typing import NamedTuple

    class TS(NamedTuple):
        params: object
        opt: object
        err: object
    return TS(params=params, opt=opt, err=err)


@pytest.mark.parametrize("opt_name", ["adamw", "lamb"])
def test_packed_pytree_packed_roundtrip_bit_exact(tmp_path, opt_name):
    """packed(A) -> pytree -> packed(B) -> packed(A): every hop exact.

    LAMB shares AdamState, so the repack must be optimizer-agnostic —
    both names run the identical translation and must stay bit-exact.
    """
    params = _tree(0)
    m_tree = jax.tree.map(lambda p: 0.3 * p + 0.01, _tree(1))
    v_tree = jax.tree.map(lambda p: jnp.abs(p) * 0.2, _tree(2))
    lo_a = bkt.build_layout(params, bucket_mb=1e-4, multiple_of=8)
    lo_b = bkt.build_layout(params, bucket_mb=4e-4, multiple_of=32)
    assert (lo_a.num_buckets, lo_a.bucket_elems) != (lo_b.num_buckets,
                                                     lo_b.bucket_elems)
    m_a = np.asarray(bkt.pack_buckets(m_tree, lo_a))
    v_a = np.asarray(bkt.pack_buckets(v_tree, lo_a))
    step = jnp.asarray(7, jnp.int32)

    def packed_state(lo, m, v):
        return _mk_state(params, adam.AdamState(step=step, m=m, v=v))

    def tree_template():
        return _mk_state(params, adam.AdamState(
            step=step, m=jax.tree.map(jnp.zeros_like, m_tree),
            v=jax.tree.map(jnp.zeros_like, v_tree)))

    mgr = CheckpointManager(str(tmp_path / opt_name))
    rec = bkt.layout_record(lo_a)
    mgr.save(1, packed_state(lo_a, m_a, v_a),
             meta={"format": {"version": repack.FORMAT_VERSION,
                              "state": "packed",
                              "packed_fields": ["opt/m", "opt/v"],
                              "layout": rec}},
             block=True)
    # packed(A) -> pytree
    as_tree, _ = mgr.restore(tree_template())
    for got, want in zip(jax.tree.leaves(as_tree.opt.m),
                         jax.tree.leaves(m_tree)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for got, want in zip(jax.tree.leaves(as_tree.opt.v),
                         jax.tree.leaves(v_tree)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # packed(A) -> packed(B)
    zb = jnp.zeros((lo_b.num_buckets, lo_b.bucket_elems))
    as_b, _ = mgr.restore(packed_state(lo_b, zb, zb))
    np.testing.assert_array_equal(
        np.asarray(as_b.opt.m),
        np.asarray(bkt.pack_buckets(m_tree, lo_b)))
    # pytree -> packed(B) (save the unpacked form, restore packed)
    mgr2 = CheckpointManager(str(tmp_path / (opt_name + "_tree")))
    mgr2.save(2, as_tree, block=True)
    back_b, _ = mgr2.restore(packed_state(lo_b, zb, zb))
    np.testing.assert_array_equal(
        np.asarray(back_b.opt.m),
        np.asarray(bkt.pack_buckets(m_tree, lo_b)))
    # packed(B) -> packed(A) closes the loop
    mgr3 = CheckpointManager(str(tmp_path / (opt_name + "_b")))
    mgr3.save(3, back_b, block=True)
    back_a, _ = mgr3.restore(packed_state(
        lo_a, jnp.zeros_like(m_a), jnp.zeros_like(v_a)))
    np.testing.assert_array_equal(np.asarray(back_a.opt.m), m_a)
    np.testing.assert_array_equal(np.asarray(back_a.opt.v), v_a)


def test_err_state_repack_same_ranks_exact_rank_change_conserves(
        tmp_path):
    params = _tree(0)
    lo_a = bkt.build_layout(params, bucket_mb=1e-4, multiple_of=8)
    lo_b = bkt.build_layout(params, bucket_mb=4e-4, multiple_of=32)
    rng = np.random.default_rng(0)
    err = np.zeros((2, lo_a.num_buckets, lo_a.bucket_elems), np.float32)
    # data region random, padding tail stays zero (the reachable state)
    flat = rng.standard_normal((2, lo_a.total)).astype(np.float32)
    err.reshape(2, -1)[:, :lo_a.total] = flat
    state = _mk_state(params, adam.AdamState(
        step=jnp.int32(1),
        m=jnp.asarray(bkt.pack_buckets(params, lo_a)),
        v=jnp.asarray(bkt.pack_buckets(params, lo_a))), err=err)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, state, block=True)

    zb = jnp.zeros((lo_b.num_buckets, lo_b.bucket_elems))
    tmpl_same = _mk_state(params, adam.AdamState(step=jnp.int32(1),
                                                 m=zb, v=zb),
                          err=np.zeros((2, lo_b.num_buckets,
                                        lo_b.bucket_elems), np.float32))
    got, _ = mgr.restore(tmpl_same)
    np.testing.assert_array_equal(
        np.asarray(got.err).reshape(2, -1)[:, :lo_a.total], flat)
    # rank-count change: per-rank split has no exact image — the SUM
    # (the quantity that re-enters future gradients) is conserved on
    # rank 0
    tmpl_one = tmpl_same._replace(err=np.zeros(
        (1, lo_b.num_buckets, lo_b.bucket_elems), np.float32))
    got1, _ = mgr.restore(tmpl_one)
    np.testing.assert_allclose(
        np.asarray(got1.err).reshape(1, -1)[0, :lo_a.total],
        flat.sum(axis=0), rtol=1e-6)   # 1 target rank: sum = its extent
    # a checkpoint without residual state restores with FRESH zeros
    mgr2 = CheckpointManager(str(tmp_path / "noerr"))
    mgr2.save(1, _mk_state(params, adam.AdamState(
        step=jnp.int32(1), m=zb, v=zb)), block=True)
    fresh, _ = mgr2.restore(tmpl_same)
    assert not np.asarray(fresh.err).any()


# --------------------------------------------------------------------------
# v3 per-host sharded saves + crash-consistent manifests (tentpole)
# --------------------------------------------------------------------------


def _packed_state(lo, seed=0, err_ranks=2):
    params = _tree(seed)
    m = bkt.pack_buckets(
        jax.tree.map(lambda p: 0.3 * p + 0.01, _tree(seed + 1)), lo)
    v = bkt.pack_buckets(
        jax.tree.map(lambda p: jnp.abs(p) * 0.2, _tree(seed + 2)), lo)
    err = np.zeros((err_ranks, lo.num_buckets, lo.bucket_elems),
                   np.float32)
    rng = np.random.default_rng(seed)
    err.reshape(err_ranks, -1)[:, :lo.total] = rng.standard_normal(
        (err_ranks, lo.total)).astype(np.float32)
    return _mk_state(params, adam.AdamState(step=jnp.int32(3), m=m, v=v),
                     err=err)


def _fmt_for(lo, hosts):
    return {"version": repack.FORMAT_VERSION, "state": "packed",
            "packed_fields": ["opt/m", "opt/v"],
            "layout": bkt.layout_record(lo, hosts=hosts),
            "hosts": hosts, "overlap": "buckets"}


def test_host_shard_extents_balanced_and_recorded():
    assert bkt.host_shard_extents(10, 3) == ((0, 4), (4, 7), (7, 10))
    assert bkt.host_shard_extents(2, 4) == ((0, 1), (1, 2), (2, 2),
                                            (2, 2))
    with pytest.raises(ValueError, match="hosts"):
        bkt.host_shard_extents(5, 0)
    lo = bkt.build_layout(_tree(), bucket_mb=1e-4, multiple_of=8)
    rec = bkt.layout_record(lo, hosts=2)
    assert rec["hosts"] == 2
    assert [tuple(e) for e in rec["host_extents"]] == \
        list(bkt.host_shard_extents(lo.num_buckets, 2))
    # extents are write-time provenance, not grid: fingerprint unchanged
    assert rec["fingerprint"] == bkt.layout_record(lo)["fingerprint"]


def test_v3_sharded_save_matches_gathered_v2_bit_exact(tmp_path):
    """Tentpole acceptance: each host writes only its own shard file,
    the manifest records sizes/checksums/extents, and restore through
    the assembled stream is bit-identical to a gathered v2 save of the
    same state — into the same grid, a re-gridded packed layout, and
    the pytree (non-overlap) layout."""
    params = _tree(0)
    lo_a = bkt.build_layout(params, bucket_mb=1e-4, multiple_of=8)
    lo_b = bkt.build_layout(params, bucket_mb=4e-4, multiple_of=32)
    state = _packed_state(lo_a)
    fmt = _fmt_for(lo_a, hosts=2)

    mgr2 = CheckpointManager(str(tmp_path / "v2"))
    mgr2.save(1, state, meta={"format": dict(fmt)}, format_version=2,
              block=True)
    mgr3 = CheckpointManager(str(tmp_path / "v3"))
    mgr3.save(1, state, meta={"format": dict(fmt)}, block=True)

    d3 = tmp_path / "v3" / "step_0000000001"
    assert (d3 / "manifest.json").exists()
    assert (d3 / "arrays_host0.npz").exists()
    assert (d3 / "arrays_host1.npz").exists()
    assert not (d3 / "arrays.npz").exists()
    d2 = tmp_path / "v2" / "step_0000000001"
    assert (d2 / "arrays.npz").exists()
    assert not (d2 / "manifest.json").exists()

    man = json.loads((d3 / "manifest.json").read_text())
    assert man["hosts"] == 2 and man["format_version"] == 3
    assert "meta.json" in man["files"]
    for fname, rec in man["files"].items():
        assert (d3 / fname).stat().st_size == rec["bytes"]
        assert len(rec["sha256"]) == 64
    # packed stacks split by bucket rows along the layout extents,
    # the err stack by rank
    h0 = man["files"]["arrays_host0.npz"]["keys"]
    h1 = man["files"]["arrays_host1.npz"]["keys"]
    ext = fmt["layout"]["host_extents"]
    assert h0["opt/m"]["rows"] == ext[0]
    assert h1["opt/m"]["rows"] == ext[1]
    assert h0["err"]["rows"] == [0, 1] and h1["err"]["rows"] == [1, 2]

    zb_a = jnp.zeros((lo_a.num_buckets, lo_a.bucket_elems))
    zb_b = jnp.zeros((lo_b.num_buckets, lo_b.bucket_elems))
    err_a = np.zeros((2, lo_a.num_buckets, lo_a.bucket_elems),
                     np.float32)
    err_b = np.zeros((2, lo_b.num_buckets, lo_b.bucket_elems),
                     np.float32)
    templates = {
        "packed-same": _mk_state(params, adam.AdamState(
            step=jnp.int32(0), m=zb_a, v=zb_a), err=err_a),
        "packed-regrid": _mk_state(params, adam.AdamState(
            step=jnp.int32(0), m=zb_b, v=zb_b), err=err_b),
        "pytree": _mk_state(params, adam.AdamState(
            step=jnp.int32(0),
            m=jax.tree.map(jnp.zeros_like, params),
            v=jax.tree.map(jnp.zeros_like, params)), err=err_a),
    }
    for tag, tmpl in templates.items():
        a, _ = mgr2.restore(tmpl)
        b, _ = mgr3.restore(tmpl)
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=tag)


def test_v2_to_v3_migration_roundtrip_bit_exact(tmp_path):
    """A legacy gathered v2 checkpoint restores, re-saves as sharded
    v3, and restores again — every leaf bit-identical to the source."""
    params = _tree(0)
    lo = bkt.build_layout(params, bucket_mb=1e-4, multiple_of=8)
    state = _packed_state(lo)
    fmt = _fmt_for(lo, hosts=2)
    tmpl = jax.tree.map(np.zeros_like, jax.device_get(state))

    old = CheckpointManager(str(tmp_path / "old"))
    old.save(1, state, meta={"format": dict(fmt)}, format_version=2,
             block=True)
    from_v2, meta_v2 = old.restore(tmpl)
    assert meta_v2["format"]["version"] == 2

    new = CheckpointManager(str(tmp_path / "new"))
    new.save(1, from_v2, meta={"format": dict(fmt)}, block=True)
    from_v3, meta_v3 = new.restore(tmpl)
    assert meta_v3["format"]["version"] == 3
    for x, y in zip(jax.tree.leaves(jax.device_get(state)),
                    jax.tree.leaves(from_v3)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("corrupt", ["truncate", "flip", "del_manifest"])
def test_v3_fault_injection_rejects_step_and_falls_back(tmp_path,
                                                        corrupt, caplog):
    """Durability satellite: truncate a shard / flip a byte / delete
    manifest.json after commit — restore rejects the step via the
    manifest validation and falls back to the previous committed one;
    an explicitly requested corrupt step raises."""
    params = _tree(0)
    lo = bkt.build_layout(params, bucket_mb=1e-4, multiple_of=8)
    fmt = _fmt_for(lo, hosts=2)
    mgr = CheckpointManager(str(tmp_path), keep=5)
    s1 = _packed_state(lo, seed=0)
    s2 = _packed_state(lo, seed=7)
    mgr.save(1, s1, meta={"format": dict(fmt)}, block=True)
    mgr.save(2, s2, meta={"format": dict(fmt)}, block=True)

    d2 = tmp_path / "step_0000000002"
    shard = d2 / "arrays_host1.npz"
    if corrupt == "truncate":
        shard.write_bytes(shard.read_bytes()[:shard.stat().st_size // 2])
    elif corrupt == "flip":
        data = bytearray(shard.read_bytes())
        data[len(data) // 2] ^= 0xFF
        shard.write_bytes(bytes(data))
    else:
        (d2 / "manifest.json").unlink()

    zb = jnp.zeros((lo.num_buckets, lo.bucket_elems))
    tmpl = _mk_state(params, adam.AdamState(step=jnp.int32(0), m=zb,
                                            v=zb),
                     err=np.zeros((2, lo.num_buckets, lo.bucket_elems),
                                  np.float32))
    with pytest.raises(CheckpointCorruptError):
        mgr.restore(tmpl, step=2)
    with caplog.at_level(logging.WARNING,
                         logger="repro.checkpoint.checkpoint"):
        got, meta = mgr.restore(tmpl)
    assert meta["step"] == 1
    np.testing.assert_array_equal(np.asarray(got.opt.m),
                                  np.asarray(s1.opt.m))
    assert any("falling back" in r.message for r in caplog.records)


def test_restore_rejects_lossy_dtype_cast_unless_allowed(tmp_path,
                                                         caplog):
    """`_unflatten_like` no longer astype()s silently: fp32 ckpt into a
    bf16 template raises unless allow_cast=True, and ANY cast logs."""
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"w": jnp.arange(4.0, dtype=jnp.float32)}, block=True)
    narrow = {"w": jax.ShapeDtypeStruct((4,), jnp.bfloat16)}
    with pytest.raises(ValueError, match="lossy dtype cast"):
        mgr.restore(narrow)
    with caplog.at_level(logging.WARNING,
                         logger="repro.checkpoint.checkpoint"):
        got, _ = mgr.restore(narrow, allow_cast=True)
    assert np.asarray(got["w"]).dtype == jnp.bfloat16
    assert any("cast" in r.message for r in caplog.records)
    # widening is lossless: allowed without the flag, still logged
    caplog.clear()
    with caplog.at_level(logging.WARNING,
                         logger="repro.checkpoint.checkpoint"):
        wide, _ = mgr.restore({"w": jax.ShapeDtypeStruct((4,),
                                                         np.float64)})
    assert np.asarray(wide["w"]).dtype == np.float64
    assert any("cast" in r.message for r in caplog.records)
    # same dtype: no cast, no log
    caplog.clear()
    with caplog.at_level(logging.WARNING,
                         logger="repro.checkpoint.checkpoint"):
        mgr.restore({"w": jax.ShapeDtypeStruct((4,), np.float32)})
    assert not caplog.records


def test_all_steps_skips_stray_entries_with_one_warning(tmp_path,
                                                        caplog):
    """Stray step_* entries (editor leftovers) are skipped with a
    warning instead of crashing int() — and warned only once."""
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"w": jnp.ones(2)}, block=True)
    os.makedirs(str(tmp_path / "step_00000000xx"))
    (tmp_path / "step_editor.swp").write_text("junk")
    with caplog.at_level(logging.WARNING,
                         logger="repro.checkpoint.checkpoint"):
        assert mgr.all_steps() == [1]
    assert sum("non-checkpoint" in r.message
               for r in caplog.records) == 2
    caplog.clear()
    with caplog.at_level(logging.WARNING,
                         logger="repro.checkpoint.checkpoint"):
        assert mgr.all_steps() == [1]
    assert not caplog.records


def test_err_rank_change_distributes_sum_across_new_ranks(tmp_path):
    """Re-mesh residual bugfix: the summed residual is partitioned over
    the NEW ranks' contiguous stream extents — sum conserved
    bit-exactly, every destination rank carries a share, no rank parked
    with the whole fleet's residual (the old rank-0 behavior)."""
    params = _tree(0)
    lo = bkt.build_layout(params, bucket_mb=1e-4, multiple_of=8)
    state = _packed_state(lo, seed=3, err_ranks=4)
    flat = np.asarray(state.err).reshape(4, -1)[:, :lo.total].copy()
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, state, block=True)

    zb = jnp.zeros((lo.num_buckets, lo.bucket_elems))
    tmpl = _mk_state(params, adam.AdamState(step=jnp.int32(1), m=zb,
                                            v=zb),
                     err=np.zeros((2, lo.num_buckets, lo.bucket_elems),
                                  np.float32))
    got, _ = mgr.restore(tmpl)
    got_err = np.asarray(got.err).reshape(2, -1)
    np.testing.assert_array_equal(got_err.sum(axis=0)[:lo.total],
                                  flat.sum(axis=0))
    exts = bkt.host_shard_extents(lo.padded_total, 2)
    for r, (lo_e, hi_e) in enumerate(exts):
        assert np.abs(got_err[r, lo_e:min(hi_e, lo.total)]).sum() > 0, \
            f"rank {r} restarted with an empty residual share"
        outside = np.concatenate([got_err[r, :lo_e], got_err[r, hi_e:]])
        assert not outside.any(), \
            f"rank {r} holds residual outside its extent"


# --------------------------------------------------------------------------
# meta serialization + crash atomicity (satellites)
# --------------------------------------------------------------------------


def test_meta_plan_roundtrips_structured(tmp_path):
    """No more default=str: the plan comes back as a real CapacityPlan
    and numpy values as JSON numbers."""
    mgr = CheckpointManager(str(tmp_path))
    plan = plan_capacities(16, [2, 1, 1])
    mgr.save(5, {"w": jnp.ones(2)},
             meta={"plan": plan, "epoch": np.int64(3),
                   "caps": np.asarray([2.0, 1.0])}, block=True)
    _, meta = mgr.restore({"w": jnp.ones(2)})
    got = meta["plan"]
    assert isinstance(got, CapacityPlan)
    np.testing.assert_array_equal(got.rows_per_rank, plan.rows_per_rank)
    np.testing.assert_array_equal(got.capacities, plan.capacities)
    assert got.buffer_rows == plan.buffer_rows
    assert got.global_rows == plan.global_rows
    assert meta["epoch"] == 3 and meta["caps"] == [2.0, 1.0]
    # the restored plan is USABLE, not a string
    assert got.row_weights().shape == (3, plan.buffer_rows)


def test_adapt_arrays_validates_pipeline_stage_block(tmp_path):
    """A checkpoint's recorded pipeline stage plan is placement
    metadata — params are per-leaf, so restoring across stage plans
    needs NO translation and must round-trip bit-exactly — but a
    malformed record means the writer was broken, and the restore must
    fail loudly instead of resuming from a suspect checkpoint."""
    from repro.core import pipeline as pipe

    rec = pipe.stage_record(pipe.plan_stages(4, (3.0, 1.0)))
    tree = _tree(3)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(2, tree,
             meta={"format": {"version": repack.FORMAT_VERSION,
                              "state": "pytree", "packed_fields": [],
                              "layout": None, "pipeline": rec}},
             block=True)
    got, meta = mgr.restore(tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the record survives JSON round-trip into a usable StagePlan
    back = pipe.stage_from_record(meta["format"]["pipeline"])
    assert back.layers_per_stage.tolist() == [3, 1]

    # malformed blocks fail adapt loudly (broken writer)
    arrays = repack.flatten_with_paths(
        jax.tree.map(np.asarray, tree))
    ok = repack.adapt_arrays(dict(arrays), tree,
                             fmt={"pipeline": rec})
    assert set(ok) == set(arrays)
    for bad in ("stages=2",
                {"num_layers": 4},
                {"num_layers": 5, "plan": rec["plan"]}):
        with pytest.raises(ValueError, match="malformed|sums to"):
            repack.adapt_arrays(dict(arrays), tree,
                                fmt={"pipeline": bad})


def test_meta_unserializable_value_fails_loudly(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    with pytest.raises(TypeError, match="not JSON-serializable"):
        mgr.save(1, {"w": jnp.ones(2)}, meta={"bad": {1, 2}}, block=True)


def test_interrupted_write_leaves_no_done_and_restore_skips(tmp_path,
                                                            monkeypatch):
    mgr = CheckpointManager(str(tmp_path), keep=3)
    state = {"w": jnp.arange(4.0)}
    mgr.save(1, state, block=True)

    # crash mid-write (after arrays.npz, before _DONE): no commit marker
    real_savez = np.savez

    def boom(path, **kw):
        real_savez(path, **kw)
        raise RuntimeError("disk died")
    monkeypatch.setattr(np, "savez", boom)
    mgr.save(2, {"w": jnp.arange(4.0) * 2})
    with pytest.raises(RuntimeError, match="disk died"):
        mgr.wait()
    monkeypatch.setattr(np, "savez", real_savez)

    assert mgr.all_steps() == [1]          # step 2 never committed
    restored, meta = mgr.restore(state)
    assert meta["step"] == 1
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.arange(4.0))
    # a half-renamed dir without _DONE is also ignored
    os.makedirs(str(tmp_path / "step_0000000009"))
    assert mgr.latest_step() == 1


# --------------------------------------------------------------------------
# consumed-row resume validation (satellite)
# --------------------------------------------------------------------------


def test_validate_resume_equivalence_checks_assignment_not_just_total():
    a = plan_capacities(16, [1, 1, 1, 1])
    b = plan_capacities(16, [1, 1])        # re-meshed: fewer ranks, OK
    assert elastic.validate_resume_equivalence(a, b)
    assert not elastic.validate_resume_equivalence(
        a, plan_capacities(12, [1, 1]))    # different global prefix
    # same global_rows but rows that do NOT partition the prefix: the
    # old global_rows-only check passed these
    broken = CapacityPlan(capacities=np.ones(2, np.float32),
                          rows_per_rank=np.asarray([10, 4], np.int64),
                          buffer_rows=8, global_rows=16)
    assert not elastic.validate_resume_equivalence(a, broken)
    dropped = CapacityPlan(capacities=np.ones(2, np.float32),
                           rows_per_rank=np.asarray([8, 4], np.int64),
                           buffer_rows=8, global_rows=16)
    assert not elastic.validate_resume_equivalence(a, dropped)


def test_plan_remesh_buffer_divides_post_scale_accum():
    """The restart multiplies accum_steps by accum_scale, so the new
    buffer must divide by the PRODUCT (a max() left accum 2 x scale 2
    = 4 microbatches over a buffer rounded to 2)."""
    topo = elastic.MeshTopology(pods=2, data_per_pod=2, model=1)
    dec = elastic.plan_remesh(topo, [0], global_rows=12,
                              round_buffer_to=2)
    assert dec.restart_required and dec.accum_scale == 2
    assert dec.plan.buffer_rows % (2 * dec.accum_scale) == 0
    assert dec.plan.global_rows == 12


def test_checkpoint_format_block_records_layout():
    import dataclasses
    from repro.configs import base as cfgs
    from repro.configs.base import HetConfig, TrainConfig
    from repro.launch import steps
    from repro.models.model import build_model

    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = cfgs.smoke_config("olmo-1b")
    model = build_model(cfg)
    packed = TrainConfig(model=cfg, het=HetConfig(
        overlap="buckets", grad_reduction="bucketed_allreduce",
        bucket_mb=0.05))
    fmt = steps.checkpoint_format(model, packed, mesh)
    assert fmt["state"] == "packed"
    assert fmt["packed_fields"] == ["opt/m", "opt/v"]
    lo = steps.bucket_layout(model, packed, mesh)
    assert fmt["layout"]["num_buckets"] == lo.num_buckets
    assert fmt["fingerprint"] == fmt["layout"]["fingerprint"]
    assert len(fmt["layout"]["leaf_paths"]) == len(lo.sizes)

    plain = TrainConfig(model=cfg, het=HetConfig())
    fmt2 = steps.checkpoint_format(model, plain, mesh)
    assert fmt2["state"] == "pytree" and fmt2["layout"] is None


# --------------------------------------------------------------------------
# the acceptance bar: overlap checkpoint -> three-way restore
# --------------------------------------------------------------------------


@pytest.mark.slow
def test_overlap_checkpoint_three_way_restore_bit_identical():
    """Save under overlap="buckets"; restore into (i) overlap="none",
    (ii) a different bucket_mb, (iii) a re-meshed pod count after
    plan_remesh (accum-scaled to preserve the microbatch grid). In all
    three the continued trajectory is bit-identical to the
    uninterrupted run."""
    out = run_child("""
        import dataclasses, tempfile
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.configs import base
        from repro.configs.base import TrainConfig, HetConfig, \\
            OptimizerConfig, ShapeConfig
        from repro.models.model import build_model
        from repro.launch import steps
        from repro.launch.sharding import named
        from repro.launch.mesh import make_mesh
        from repro.core import capacity, dummy, elastic
        from repro.checkpoint.checkpoint import CheckpointManager
        from repro.data import synthetic

        cfg = dataclasses.replace(base.smoke_config("olmo-1b"),
                                  compute_dtype="float32")
        m = build_model(cfg)
        shape = ShapeConfig("t", 16, 2, "train")
        ocfg = OptimizerConfig(lr=1e-3, warmup_steps=2, grad_clip=0.0)
        rec = synthetic.make_lm_records(6, 17, cfg.vocab_size, seed=5)

        def tcfg_for(bucket_mb, overlap, accum=1):
            return TrainConfig(model=cfg, shape=shape,
                het=HetConfig(grad_reduction="bucketed_allreduce",
                              bucket_mb=bucket_mb, overlap=overlap,
                              accum_steps=accum),
                optimizer=ocfg)

        def batch_for(plan, lo, hi):
            packed = dummy.pack_global_batch(
                {"inputs": rec["inputs"][lo:hi, :16],
                 "labels": rec["labels"][lo:hi, :16]}, plan)
            return {k: jnp.asarray(v) for k, v in packed.items()}

        # uninterrupted run: 2-pod mesh, overlap pipeline, ckpt @ step 1
        meshA = make_mesh((2, 1, 2), ("pod", "data", "model"))
        topoA = elastic.MeshTopology(pods=2, data_per_pod=1, model=2)
        planA = capacity.plan_capacities(2, [1, 1])
        tA = tcfg_for(0.05, "buckets")
        with jax.set_mesh(meshA):
            st = steps.init_train_state(m, tA, meshA,
                                        jax.random.PRNGKey(0))
            fA = steps.build_train_step(m, tA, meshA)
            st, _ = fA(st, batch_for(planA, 0, 2))
            host1 = jax.device_get(st)
            st, met2 = fA(st, batch_for(planA, 2, 4))
            st, met3 = fA(st, batch_for(planA, 4, 6))
        ref = jax.device_get(st)
        ref_losses = (float(met2["loss"]), float(met3["loss"]))

        d = tempfile.mkdtemp()
        mgr = CheckpointManager(d)
        mgr.save(1, host1,
                 meta={"plan": planA,
                       "format": steps.checkpoint_format(m, tA, meshA)},
                 block=True)

        def resume(tcfg, mesh, plan):
            host, meta = mgr.restore(steps.state_shapes(m, tcfg, mesh))
            assert elastic.validate_resume_equivalence(meta["plan"],
                                                       plan)
            with jax.set_mesh(mesh):
                sr = jax.device_put(
                    host, named(mesh, steps.state_specs(m, tcfg, mesh)))
                f = steps.build_train_step(m, tcfg, mesh)
                sr, m2 = f(sr, batch_for(plan, 2, 4))
                sr, m3 = f(sr, batch_for(plan, 4, 6))
            return (jax.device_get(sr),
                    (float(m2["loss"]), float(m3["loss"])))

        def assert_bitwise(got, losses, tag):
            assert losses == ref_losses, (tag, losses, ref_losses)
            for a, b in zip(jax.tree.leaves(ref.params),
                            jax.tree.leaves(got.params)):
                np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b), err_msg=tag)
            print(tag, "bit-identical")

        # (i) overlap="none": moments unpack into the pytree layout
        got, losses = resume(tcfg_for(0.05, "none"), meshA, planA)
        assert_bitwise(got, losses, "overlap->none")
        # moments too: repacked pytree moments == uninterrupted packed
        lo = steps.bucket_layout(m, tA, meshA)
        from repro.core import buckets as bkt
        np.testing.assert_array_equal(
            np.asarray(bkt.pack_buckets(got.opt.m, lo)),
            np.asarray(ref.opt.m))

        # (ii) different bucket_mb: packed -> packed re-grid
        tB = tcfg_for(0.02, "buckets")
        loB = steps.bucket_layout(m, tB, meshA)
        assert (loB.num_buckets, loB.bucket_elems) != \\
            (lo.num_buckets, lo.bucket_elems)
        got, losses = resume(tB, meshA, planA)
        assert_bitwise(got, losses, "bucket_mb regrid")

        # (iii) pod lost -> plan_remesh -> 1-pod mesh, accum-scaled to
        # preserve the microbatch grid (elastic.RemeshDecision)
        dec = elastic.plan_remesh(topoA, [0], planA.global_rows)
        assert dec.restart_required and dec.accum_scale == 2
        assert elastic.validate_resume_equivalence(planA, dec.plan)
        meshC = make_mesh(dec.topology.mesh_shape(),
                              dec.topology.mesh_axes())
        tC = tcfg_for(0.02, "buckets", accum=dec.accum_scale)
        loC = steps.bucket_layout(m, tC, meshC)
        assert (loC.num_buckets, loC.bucket_elems) != \\
            (lo.num_buckets, lo.bucket_elems)        # re-grid too
        got, losses = resume(tC, meshC, dec.plan)
        assert_bitwise(got, losses, "re-mesh 2pods->1pod")
        print("OK")
        """, devices=4, timeout=1200)
    assert "OK" in out


@pytest.mark.slow
def test_train_driver_elastic_restart_with_repack(tmp_path):
    """Full driver: overlap checkpoints on a 2-pod mesh, a pod dies
    (--kill-pod), soft replanning overflows -> RemeshRequired -> the
    driver re-meshes via plan_remesh, repacks the packed optimizer
    state into the new bucket grid, and finishes the step budget."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.train",
         "--arch", "olmo-1b", "--smoke", "--steps", "12",
         "--global-batch", "16", "--seq-len", "16",
         "--devices", "2,2,2",
         "--grad-reduction", "bucketed_allreduce",
         "--bucket-mb", "0.05", "--overlap", "buckets",
         "--replan-interval", "8", "--ckpt-every", "4",
         "--kill-pod", "1@5", "--log-every", "4",
         "--ckpt-dir", str(tmp_path / "ckpt"),
         "--data-dir", str(tmp_path / "data")],
        capture_output=True, text=True, env=env, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = proc.stdout
    assert "remesh:" in out and "re-meshed to" in out, out
    assert "accum_steps scaled x2" in out, out
    assert "done:" in out, out
