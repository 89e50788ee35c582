"""An architecture joins the benchmark by files alone: its configuration
names its reference module, the harness calls that module for the check
and the FLOP counts, and builds the program's nested configuration as
the file states. On the CPU at small sizes."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_tiny  # noqa: E402
from benchlib import program, spec  # noqa: E402

# a stand-in architecture's reference: the dense one under another name,
# recording which of its functions the harness calls, modelling every
# mechanism, and counting twice the dense FLOPs
STANDIN = '''
from benchlib import reference as dense

CALLS = []


def unmodelled(mc):
    CALLS.append("unmodelled")
    return []


def served_logit_gaps(*args, **kwargs):
    CALLS.append("served_logit_gaps")
    return dense.served_logit_gaps(*args, **kwargs)


def train_reference(*args, **kwargs):
    CALLS.append("train_reference")
    return dense.train_reference(*args, **kwargs)


def train_flops_per_token(cfg, seq_len):
    return 2 * dense.train_flops_per_token(cfg, seq_len)


def decode_flops(cfg, contexts):
    return 2 * dense.decode_flops(cfg, contexts)


def prefill_flops(cfg, prompt_lens):
    return 2 * dense.prefill_flops(cfg, prompt_lens)
'''

MOE = {"num_experts": 8, "top_k": 2, "expert_d_ff": 32,
       "num_shared_experts": 1, "shared_d_ff": 32}
MLA = {"kv_lora_rank": 32, "rope_head_dim": 8, "nope_head_dim": 16,
       "v_head_dim": 16}


def _standin_files(tmp_path: Path, base: dict) -> dict:
    """Write the stand-in's reference module and configuration file, and
    read the configuration back as the harness would."""
    ref = tmp_path / "standin_reference.py"
    ref.write_text(STANDIN)
    cfg = dict(base, name="standin-tiny", reference=str(ref))
    path = tmp_path / "standin-tiny.json"
    path.write_text(json.dumps(cfg))
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("kind", ["train", "serve"])
def test_a_standin_architecture_runs_through_its_own_reference(tmp_path,
                                                               kind):
    if kind == "train":
        cfg = _standin_files(tmp_path, bench_tiny.olmo())
        line = bench_tiny.run(cfg, bench_tiny.train_mix(),
                              bench_tiny.TRAIN_LIMITS)
        called = "train_reference"
    else:
        cfg = _standin_files(tmp_path, bench_tiny.phi4())
        line = bench_tiny.run(cfg, bench_tiny.serve_mix(),
                              bench_tiny.SERVE_LIMITS, seconds=1.5)
        called = "served_logit_gaps"
    assert line["correct"], line["checks"]
    calls = spec.reference(cfg).CALLS
    assert calls[0] == "unmodelled" and called in calls, calls


def test_mfu_readers_count_the_flops_of_the_named_reference(tmp_path):
    cfg = _standin_files(tmp_path, spec.config("phi4-mini-3.8b"))
    peaks = {"bf16_flops_per_s": 197e12}
    serve = {"kind": "serve", "traced_contexts": [[100, 300]],
             "traced_prompts": [64], "chips": 1, "peaks": peaks,
             "window_s": 4.0}
    train = {"kind": "train", "mix": {"seq_len": 2048},
             "traced_tokens": 8192, "chips": 1, "peaks": peaks,
             "window_s": 4.0}
    for name, rec in (("mfu.serve", serve), ("mfu.train", train)):
        reader = spec.metric_reader(name)
        dense = reader.read(dict(rec, cfg=spec.config("phi4-mini-3.8b")))
        assert reader.read(dict(rec, cfg=cfg)) == pytest.approx(2 * dense)


def test_nested_fields_come_out_as_the_file_says(tmp_path):
    cfg = _standin_files(tmp_path, dict(bench_tiny.olmo(), moe=MOE,
                                        mla=MLA))
    mc = program.model_config(cfg)
    for k, v in MOE.items():
        assert getattr(mc.moe, k) == v
    for k, v in MLA.items():
        assert getattr(mc.mla, k) == v
    assert mc.moe.enabled and mc.mla.enabled
    # the dense reference models neither: the same file is refused
    dense = dict(cfg, reference=bench_tiny.olmo()["reference"])
    with pytest.raises(SystemExit, match="does not model moe, mla"):
        program.model_config(dense)


@pytest.mark.parametrize("over,named", [
    ({"moe": {"n_routed_experts": 64}}, "moe.n_routed_experts"),
    ({"mla": dict(MLA, q_lora=0)}, "mla.q_lora"),
    ({"moe": 8}, "'moe'"),
])
def test_refuses_a_nested_key_the_program_lacks(tmp_path, over, named):
    cfg = _standin_files(tmp_path, dict(bench_tiny.olmo(), **over))
    with pytest.raises(SystemExit, match=named):
        program.model_config(cfg)


@pytest.mark.parametrize("over,named", [
    ({"logit_softcap": 30.0}, "logit_softcap"),
    ({"qk_norm": True}, "qk_norm"),
    ({"moe": {"num_experts": 4}}, "moe"),
    ({"mla": {"kv_lora_rank": 16}}, "mla"),
    ({"ssm": {"state_dim": 16}}, "ssm"),
    ({"xlstm": {"enabled": True}}, "xlstm"),
    ({"hybrid": {"enabled": True}}, "hybrid"),
    ({"frontend": "embedding_stub"}, "frontend embedding_stub"),
    ({"tie_embeddings": False}, "untied embeddings"),
    ({"activation": "gelu"}, "activation gelu"),
    ({"norm": "layernorm"}, "norm layernorm"),
])
def test_dense_reference_refuses_what_it_does_not_model(over, named):
    cfg = dict(bench_tiny.olmo(), **over)
    with pytest.raises(SystemExit, match=f"does not model {named}$|"
                                         f"does not model {named},"):
        program.model_config(cfg)
