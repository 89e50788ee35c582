import os
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    # the scaling benchmarks emulate the paper's multi-node grid on
    # host devices; 8 "nodes" like the paper's largest configuration
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8")

DOC = """Benchmark suite — one entry per paper table/figure + roofline.

  scaling_translation  paper Table 3, Translation block
  scaling_bert         paper Table 3, BERT block (masked-LM weights)
  scaling_small        paper Table 3, MNIST block (negative result)
  equivalence          the HetSeq invariant, measured
  roofline_bench       §Roofline table from dry-run artifacts
  reduce_bench         per-leaf vs bucketed gradient reduction (--quick
                       smoke: fails loudly if the bucketed engine's
                       cross-pod collective count or modeled int8 DCN
                       bytes regress)
  overlap_bench        monolithic vs double-buffered per-bucket fused
                       reduce+update pipeline (--quick smoke: fails
                       loudly if the modeled overlapped step time is
                       not strictly below the serial modeled time, or
                       the fused pipeline diverges from the monolithic
                       update)
  chaos_bench          convergence under scripted faults (core/chaos.py
                       presets: sustained slowdown, dead rank, pod kill
                       + re-mesh, full storm): fails loudly if a
                       chaos-disturbed run is not bit-identical (fp32,
                       canonical-order aggregation) to the undisturbed
                       run over the same global rows, if throughput-fed
                       replanning does not strictly beat no-replan on
                       modeled wall-clock under sustained slowdown, or
                       if the seeded trace/run is not replayable
  serve_bench          continuous-batching serving engine (repro/serve:
                       paged KV cache, capacity-aware admission): fails
                       loudly if the engine's modeled tokens/sec is not
                       strictly above the static-batch baseline on the
                       same mixed-length open-loop trace, if a single
                       sequence's generated tokens are not bit-identical
                       to the contiguous-cache static path (fp32), or if
                       per-pod peak concurrency under saturation is not
                       the capacity-plan split (slower pods strictly
                       fewer sequences); includes a 3-arrival
                       mixed-length end-to-end smoke and a decode-step
                       roofline: the in-kernel-gather byte model of the
                       paged Pallas kernels (attention_impl="pallas")
                       must be strictly below materialize-then-attend
                       at every swept (max_blocks, block_size) point,
                       and the pallas engine must be token-identical to
                       the reference engine on the smoke trace
  pipeline_bench       heterogeneous pipeline parallelism
                       (HetConfig.pipeline_stages: capacity-sized
                       contiguous stages + 1F1B): fails loudly if the
                       stages=2 step is not bit-identical (fp32,
                       allreduce, clip=0) to pure DP, if the modeled
                       capacity-sized stage cut does not strictly beat
                       uniform stages AND pure DP on a 2:1 pod-speed
                       skew, or if a checkpoint saved under one stage
                       plan does not restore bit-identically into a
                       different stage plan
  durability_smoke     (--quick only) checkpoint manifest path: save ->
                       corrupt a shard / delete the manifest ->
                       checksum-validated fallback restore to the
                       previous committed step

--quick: the CI smoke tier — runs the fail-loud reduce/overlap/chaos
bench smokes plus the repo's quick test tier (``pytest -m "not slow"``: the
multi-device subprocess suites, hypothesis sweeps and driver
integration tests carry a ``slow`` marker and stay in the full tier-1
run), skipping the scaling sweeps.

Prints a ``name,us_per_call,derived`` CSV summary at the end.
"""

import argparse
import os
import subprocess
import sys
import time


def _run_quick_test_tier() -> float:
    """The -m 'not slow' pytest tier, as CI runs it. Fails loudly."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(repo, "src") +
                         os.pathsep + env.get("PYTHONPATH", ""))
    # a CPU tier: this process already holds JAX's backend, and a child
    # that reached for an accelerator would contend for it
    env["JAX_PLATFORMS"] = "cpu"
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-m", "not slow",
         "-p", "no:cacheprovider", os.path.join(repo, "tests")],
        env=env, cwd=repo)
    if proc.returncode != 0:
        raise SystemExit(f"quick test tier failed "
                         f"(exit {proc.returncode})")
    return time.time() - t0


def main() -> None:
    ap = argparse.ArgumentParser(description=DOC)
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: fail-loud bench smokes + the "
                         "-m 'not slow' pytest tier, no scaling sweeps")
    args = ap.parse_args()

    t_all = time.time()
    csv = []

    from benchmarks import (chaos_bench, equivalence, overlap_bench,
                            pipeline_bench, reduce_bench,
                            roofline_bench, scaling_bert,
                            scaling_small, scaling_translation,
                            serve_bench)

    rb = reduce_bench.main(quick=True)
    csv.append(("reduce_bench", rb["bucketed"]["avg_ms"] * 1e3,
                f"collectives_bucketed={rb['bucketed']['collectives']} "
                f"vs_per_leaf={rb['per_leaf']['collectives']}"))

    ob = overlap_bench.main(quick=True)
    csv.append(("overlap_bench", ob["fp32"]["overlap"]["avg_ms"] * 1e3,
                f"model_speedup_int8="
                f"{ob['int8']['model']['model_speedup']:.2f}x "
                f"bwd_overlap_int8="
                f"{ob['backward_int8']['model']['model_speedup_vs_after_backward']:.2f}x "
                f"exact_fp32={ob['fp32']['exact_match']}"))

    cb = chaos_bench.main(quick=args.quick)
    n_bit = sum(1 for p in cb["presets"].values()
                if p["bit_identical"])
    csv.append(("chaos_bench", 0.0,
                f"bit_identical_presets={n_bit}/{len(cb['presets'])} "
                f"replan_speedup="
                f"{cb['slowdown_wall']['speedup']:.2f}x"))

    pb = pipeline_bench.main(quick=args.quick)
    csv.append(("pipeline_bench", 0.0,
                f"exact_fp32={pb['exactness']['exact_match']} "
                f"capacity_vs_uniform="
                f"{pb['modeled']['speedup_vs_uniform']:.2f}x "
                f"vs_dp={pb['modeled']['speedup_vs_dp']:.2f}x "
                f"restore_bit_identical="
                f"{pb['restore']['bit_identical']}"))

    sv = serve_bench.main(quick=args.quick)
    rf = sv["decode_roofline"]
    csv.append(("serve_bench", 0.0,
                f"continuous_vs_static="
                f"{sv['throughput']['speedup']:.2f}x "
                f"bit_identical={sv['bit_identity']['identical']} "
                f"pod_limits={sv['routing']['pod_limits']} "
                f"block_util_peak={sv['block_util']['peak']:.2f} "
                f"roofline_kernel_beats_materialize="
                f"{rf['kernel_strictly_better']} "
                f"pallas_token_identical="
                f"{rf['measured']['token_identical']}"))

    if args.quick:
        from benchmarks import docs_smoke, durability_smoke
        n_faults = durability_smoke.run_durability_smoke()
        csv.append(("durability_smoke", 0.0,
                    f"fault_scenarios={n_faults}"))
        n_cmds = docs_smoke.run_docs_smoke()
        csv.append(("docs_smoke", 0.0, f"readme_commands={n_cmds}"))
        tier_s = _run_quick_test_tier()
        csv.append(("quick_test_tier", 0.0, f"wall_s={tier_s:.1f}"))
    else:
        res = scaling_translation.main(max_nodes=8, steps=10)
        base = res[0]
        best = min(res, key=lambda r: r.avg_step_s)
        csv.append(("scaling_translation", base.avg_step_s * 1e6,
                    f"best_speedup={base.total_s / best.total_s:.2f}x"))

        res = scaling_bert.main(max_nodes=8, steps=10)
        base = res[0]
        best = min(res, key=lambda r: r.avg_step_s)
        csv.append(("scaling_bert", base.avg_step_s * 1e6,
                    f"best_speedup={base.total_s / best.total_s:.2f}x"))

        res = scaling_small.main(max_nodes=8, steps=8)
        base = res[0]
        worst = max(res[1:], key=lambda r: r.avg_step_s) if len(res) > 1 \
            else base
        csv.append(("scaling_small", base.avg_step_s * 1e6,
                    f"overhead_at_scale="
                    f"{worst.avg_step_s / base.avg_step_s:.2f}x"))

        rows = equivalence.main(trials=6)
        worst_g = max(r[2] for r in rows)
        csv.append(("equivalence", 0.0, f"max_grad_err={worst_g:.2e}"))

        rl = roofline_bench.main()
        if rl:
            import numpy as np
            fr = [r.roofline_frac for r in rl if r.kind == "train"]
            csv.append(("roofline", 0.0,
                        f"train_cells={len(fr)} median_roofline="
                        f"{100 * float(np.median(fr)):.1f}%"))

    print("\n== CSV summary (name,us_per_call,derived) ==")
    for name, us, derived in csv:
        print(f"{name},{us:.1f},{derived}")
    print(f"[benchmarks] total {time.time() - t_all:.1f}s")


if __name__ == '__main__':
    main()
