"""Per-layer readers that restate another quantity: the per-layer
``tpot_p95_ms.decode`` is the end-to-end ``tpot_p95_ms`` of the traced
window, and ``mfu.decode.serve`` is ``mfu.decode`` under the name of a cell
whose end-to-end metric is ``serve_tokens_per_s``."""
import types

import pytest

import drive_serve
import harness


def _batches(n=5, size=4, new=9):
    return [drive_serve.Batch(i, 512, size, new, due=10.0 * i,
                              first=10.0 * i + 0.5, done=10.0 * i + 0.5 + 0.08 * (i + 1))
            for i in range(n)]


def _run(batches, trace=None, kind="serve"):
    cell = harness.cell("mamba2-serve-decode")
    return drive_serve.RunView(
        cell, harness.Spans(), (0.0, 60.0), trace,
        [types.SimpleNamespace(device_kind="TPU v5 lite")], batches, kind)


def _reader(name):
    return harness.load_module(harness.HERE / "metrics" / f"{name}.py").read


def test_tpot_reader_is_the_end_to_end_tail():
    b = _batches()
    e2e = drive_serve.end_to_end(b, 0.0, 50.0, 1.0)["tpot_p95_ms"]
    assert _reader("tpot_p95_ms.decode")(_run(b)) == e2e
    assert e2e == pytest.approx(1e3 * 0.4 / 8)     # the slowest batch


@pytest.mark.parametrize("batches, kind", [([], "serve"), (_batches(), "train")])
def test_tpot_reader_finds_nothing(batches, kind):
    assert _reader("tpot_p95_ms.decode")(_run(batches, kind=kind)) is None


def test_decode_mfu_reads_alike_under_both_names():
    b = _batches(n=2)
    steps = sum(x.new_tokens - 1 for x in b)
    trace = {"modules": {"jit__decode_step": [0.05, steps]}}
    run = _run(b, trace)
    got = _reader("mfu.decode.serve")(run)
    assert got == _reader("mfu.decode")(run)
    assert 0 < got < 100
    assert _reader("mfu.decode.serve")(_run(b, None)) is None
