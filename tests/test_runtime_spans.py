"""Spans of the serve and train loops (`repro.obs` inside `serve()` and
`train()`): their order and nesting under an installed recorder, that they
change no result, that an annotating recorder writes them into a
`jax.profiler` trace's host plane, and the compile counter it starts."""
import glob

import jax
import numpy as np
import pytest

from repro import obs
from repro.configs import get_config
from repro.configs.registry import model_module
from repro.configs.shapes import ShapeSpec
from repro.data.synthetic import make_batch
from repro.parallel.sharding import make_env
from repro.runtime.serve_loop import ServeConfig, serve
from repro.runtime.train_loop import TrainConfig, train

NEW_TOKENS = 4


class _Planner:
    """A governor that plans nothing and counts its calls."""

    def __init__(self):
        self.calls = 0

    def plan(self, region, device=None):
        self.calls += 1

    def simulate(self, regions):
        return len(regions)


@pytest.fixture(autouse=True)
def _no_leaked_recorder():
    yield
    obs.uninstall()


@pytest.fixture(scope="module")
def served():
    cfg = get_config("mamba2-130m", smoke=True)
    env = make_env(cfg, None)
    params, _ = model_module(cfg).init(jax.random.PRNGKey(0), cfg)
    batch = make_batch(cfg, ShapeSpec("t", 8, 2, "prefill"))

    def call():
        return serve(cfg, env, params, batch,
                     ServeConfig(max_new_tokens=NEW_TOKENS),
                     governor=_Planner())
    return call


def _by_start(rows):
    return sorted(rows, key=lambda r: (r["t0"], int(r["sid"].split(":")[1])))


def test_serve_spans_in_order_nested_and_tokens_unchanged(served):
    off = np.asarray(served()["tokens"])
    rec = obs.install(obs.SpanRecorder("t"))
    on = np.asarray(served()["tokens"])
    np.testing.assert_array_equal(on, off)

    rows = _by_start(rec.rows())
    assert [r["name"] for r in rows] == (
        ["serve.call", "serve.prefill", "serve.first_token", "serve.plan"]
        + ["serve.step"] * (NEW_TOKENS - 1) + ["serve.wait", "serve.plan"])
    call = rows[0]
    assert call["parent"] is None
    assert call["attrs"] == {"batch": 2, "prompt_len": 8,
                             "new_tokens": NEW_TOKENS}
    for r in rows[1:]:
        assert r["parent"] == call["sid"]
        assert call["t0"] <= r["t0"] <= r["t1"] <= call["t1"]
    assert all("attrs" not in r for r in rows if r["name"] == "serve.step")


def test_train_spans_in_order_and_nested(tmp_path):
    cfg = get_config("mamba2-130m", smoke=True)
    rec = obs.install(obs.SpanRecorder("t"))
    planner = _Planner()
    steps = 2
    m = train(cfg, ShapeSpec("t", 32, 2, "train"), make_env(cfg, None),
              TrainConfig(steps=steps, checkpoint_dir=str(tmp_path),
                          checkpoint_every=1),
              governor=planner, device=None, regions=["a", "b"],
              verbose=False)
    assert len(m["loss"]) == steps and "lr" not in m and "straggler" not in m
    assert planner.calls == 2 * steps

    rows = _by_start(rec.rows())
    per_step = ["train.iter", "train.batch", "train.step", "train.sync",
                "train.plan", "train.ckpt"]
    assert [r["name"] for r in rows] == ["train.init"] + per_step * steps
    iters = [r for r in rows if r["name"] == "train.iter"]
    assert all(r["parent"] is None for r in [rows[0]] + iters)
    for k, it in enumerate(iters):
        children = rows[2 + 6 * k: 7 + 6 * k]
        assert [r["name"] for r in children] == per_step[1:]
        for r in children:
            assert r["parent"] == it["sid"]
            assert it["t0"] <= r["t0"] <= r["t1"] <= it["t1"]


def _host_event_names(trace_dir):
    from jax.profiler import ProfileData

    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(e.name for e in line.events)
    return names


def test_annotating_recorder_writes_spans_to_the_profiler_host_plane(
        served, tmp_path):
    served()                                     # compiled outside the trace
    obs.install(obs.SpanRecorder("t", annotate=True))
    with jax.profiler.trace(str(tmp_path)):
        served()
    names = _host_event_names(tmp_path)
    assert {"serve.call", "serve.prefill", "serve.first_token", "serve.plan",
            "serve.step", "serve.wait"} <= names


def test_plain_recorder_writes_nothing_to_the_profiler(served, tmp_path):
    served()
    obs.install(obs.SpanRecorder("t"))
    with jax.profiler.trace(str(tmp_path)):
        served()
    assert not {n for n in _host_event_names(tmp_path)
                if n.startswith("serve.")}


def test_compile_counter_counts_new_shapes_only():
    def double(x):
        return 2 * x

    f = jax.jit(double)
    xs = [np.arange(3.0), np.arange(3.0) + 1, np.arange(4.0)]
    rec = obs.install(obs.SpanRecorder("t", annotate=True))

    def traces():
        return [r for r in rec.rows()
                if r["name"] == "jit.trace" and r["attrs"]["fun"] == "double"]

    f(xs[0]).block_until_ready()
    assert len(traces()) == 1
    f(xs[1]).block_until_ready()                 # same shape: no new trace
    assert len(traces()) == 1
    f(xs[2]).block_until_ready()                 # new shape: one more
    got = traces()
    assert len(got) == 2
    assert all(r["ph"] == "i" and r["cat"] == "jit" and r["attrs"]["seconds"] >= 0
               for r in got)
    assert any(r["name"] == "jit.compile" for r in rec.rows())


def test_compile_counter_records_nothing_without_a_recorder():
    rec = obs.install(obs.SpanRecorder("t", annotate=True))
    obs.uninstall()
    jax.jit(lambda x: x + 3)(np.arange(5.0)).block_until_ready()
    assert rec.rows() == []
