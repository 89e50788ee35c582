"""``pool_move_mb.serve`` (``metrics/pool_move_mb.serve.py``): it reads
the ``pool_move_bytes`` gauge on the ``serve.decode`` spans in the
traced window, nothing where the program keeps no such count, and 0 on
a traced CPU run of the tiny serving stand-in, whose decode step
carries its pool through the layer scan."""
from __future__ import annotations

import sys
import time
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_tiny  # noqa: E402
import test_program_spans as tps  # noqa: E402
from benchlib import program_spans as ps  # noqa: E402
from benchlib import spec  # noqa: E402
from benchlib import trace as trace_mod  # noqa: E402
from benchlib.window import Window  # noqa: E402
from repro import obs  # noqa: E402


def test_pool_move_reader_reads_the_decode_spans_gauge(monkeypatch):
    spans = tps._serve_program()
    monkeypatch.setattr(ps, "program_spans", lambda: spans)
    rec = tps._rec("serve", tps._serve_trace())
    reader = spec.metric_reader("pool_move_mb.serve")
    # a program that keeps no such count reads nothing
    assert reader.read(rec) is None
    # the window's steps 2 and 3 carry the gauge; the warm-up's does not
    # count
    for sp in spans:
        if sp.name == "serve.decode":
            sp.attrs["pool_move_bytes"] = 12.5e9 if sp.attrs["run"] == 1 \
                else 4.5e6
    assert reader.read(rec) == pytest.approx(4.5)
    assert reader.read(tps._rec("train", tps._serve_trace())) is None


def test_pool_move_reads_zero_on_a_traced_cpu_serve_run(tmp_path):
    import jax

    from benchlib import serve_cell

    window = Window(0.5, str(tmp_path / "trace"), time.perf_counter())
    res = serve_cell.run(bench_tiny.phi4(), bench_tiny.serve_mix(),
                         2 ** 33 + 7, jax.devices()[:1], window, None)
    tr = trace_mod.load(str(tmp_path / "trace"))
    assert ps.align(tr, obs.spans(), *ps.CALLS["serve"]) is not None
    rec = dict(res, kind="serve", trace=tr, window_s=tr.window_s)
    assert spec.metric_reader("pool_move_mb.serve").read(rec) == 0
