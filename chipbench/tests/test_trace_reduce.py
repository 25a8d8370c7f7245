"""The reduction from a profiler trace to busy time, program times and the
breakdown, on a hand-made trace and on a small trace recorded on a TPU v5e
(three decode steps of mamba2-130m at batch 4, prompt 256)."""
import gzip
import json

import pytest

import trace_reduce as T
from conftest import HERE

RECORDED = HERE / "data" / "trace-small.json.gz"


def _made():
    return {
        "device": {"/device:TPU:0": {
            "ops": [["fusion.1", 0, 10], ["dot.2", 5, 10], ["fusion.1", 30, 10],
                    ["late", 60, 5]],
            "modules": [["jit_step(7)", 0, 15], ["jit_step(7)", 30, 10]]}},
        "spans": [["cb.window", 0, 50], ["cb.serve", 0, 50], ["cb.plan", 16, 12]],
    }


def test_busy_union_clips_to_the_window():
    r = T.reduce(_made())
    assert r["window_s"] == pytest.approx(50e-9)
    assert r["busy_s"] == pytest.approx(25e-9)     # [0,15] and [30,40]


def test_programs_by_stable_name():
    r = T.reduce(_made())
    assert r["modules"] == {"jit_step": [pytest.approx(25e-9), 2]}
    assert T.module_seconds(r, "jit_step") == (pytest.approx(25e-9), 2)


def test_idle_gaps_named_by_innermost_host_span():
    gaps = dict(T.reduce(_made())["breakdown"]["idle_gaps"])
    assert gaps == {"cb.plan": pytest.approx(15e-9),
                    "cb.serve": pytest.approx(10e-9)}


def test_top_ops_sum_durations_by_name():
    ops = T.reduce(_made())["breakdown"]["device_ops"]
    assert ops[0] == ["fusion.1", pytest.approx(20e-9)]
    assert len(ops) == 2                            # "late" is outside


def test_no_window_span_is_an_error():
    tr = _made()
    tr["spans"] = tr["spans"][1:]
    with pytest.raises(ValueError, match="cb.window"):
        T.reduce(tr)


def test_recorded_trace():
    with gzip.open(RECORDED, "rt") as f:
        tr = json.load(f)
    r = T.reduce(tr)
    assert 0 < r["busy_s"] <= r["window_s"]
    decode_s, calls = T.module_seconds(r, "jit__decode_step")
    assert calls == 3 and 0 < decode_s < r["busy_s"]
    assert T.module_seconds(r, "jit__prefill")[1] == 1
    names = dict(r["breakdown"]["idle_gaps"])
    assert set(names) <= {"cb.window", "cb.serve", "cb.plan"}
    assert all(" " not in op for op, _ in r["breakdown"]["device_ops"])


def test_op_names_drop_the_hlo_text():
    assert T.op_name("%fusion.12 = bf16[4]{0} fusion(bf16[4]{0} %p)") == "fusion.12"
    assert T.op_name("copy-start") == "copy-start"
