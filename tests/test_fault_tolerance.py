"""Heartbeats, straggler policy, retry, elastic remesh."""
import pytest

from repro.runtime.fault_tolerance import (HeartbeatMonitor, StragglerPolicy,
                                           elastic_remesh, retry_step)


def test_heartbeat_detects_dead_worker():
    clock = [0.0]
    hb = HeartbeatMonitor(3, timeout_s=10.0, clock=lambda: clock[0])
    clock[0] = 5.0
    hb.beat(0); hb.beat(1)
    clock[0] = 12.0
    assert hb.dead() == [2]
    clock[0] = 30.0
    assert set(hb.dead()) == {0, 1, 2}


def test_heartbeat_zero_workers_edge():
    hb = HeartbeatMonitor(0, timeout_s=1.0, clock=lambda: 99.0)
    assert hb.dead() == []                     # nothing tracked, nothing dead
    hb.beat(7)                                 # never registered: ignored
    assert hb.dead() == []
    hb.register(7)
    assert hb.dead() == []


def test_heartbeat_beat_after_dead_is_dropped():
    """A worker reaped after a timeout must stay gone: a late beat from
    the zombie process cannot resurrect it into the liveness map."""
    clock = [0.0]
    hb = HeartbeatMonitor(2, timeout_s=10.0, clock=lambda: clock[0])
    clock[0] = 15.0
    assert set(hb.dead()) == {0, 1}
    hb.remove(0)                               # driver reaps it
    hb.beat(0)                                 # zombie's queued beat arrives
    assert hb.dead() == [1]
    assert 0 not in hb.last
    hb.register(0)                             # an EXPLICIT replacement is
    assert hb.dead() == [1]                    # tracked from now


def test_heartbeat_dynamic_register_uses_injected_clock():
    clock = [100.0]
    hb = HeartbeatMonitor(0, timeout_s=5.0, clock=lambda: clock[0])
    hb.register("w0")
    clock[0] = 104.0
    hb.register("w1")
    clock[0] = 106.0
    assert hb.dead() == ["w0"]
    hb.beat("w0")
    assert hb.dead() == []
    hb.remove("missing")                       # idempotent


def test_heartbeat_rejects_nonpositive_timeout():
    with pytest.raises(ValueError, match="timeout"):
        HeartbeatMonitor(2, timeout_s=0.0)


def test_straggler_policy_evicts_after_budget():
    sp = StragglerPolicy(ratio=1.5, budget=3)
    for _ in range(10):
        assert sp.observe(1.0) == "ok"
    verdicts = [sp.observe(5.0) for _ in range(3)]
    assert verdicts == ["degraded", "degraded", "evict"]
    # healthy step resets the counter
    sp2 = StragglerPolicy(ratio=1.5, budget=3)
    [sp2.observe(1.0) for _ in range(5)]
    sp2.observe(5.0)
    sp2.observe(1.0)
    assert sp2.observe(5.0) == "degraded"


def test_straggler_ewma_not_poisoned():
    sp = StragglerPolicy(ratio=1.5, budget=100)
    [sp.observe(1.0) for _ in range(5)]
    [sp.observe(10.0) for _ in range(5)]       # stragglers
    assert sp._ewma < 1.5                      # EWMA ignored the spikes


def test_straggler_in_flight_tracking_monotonic_clock():
    """start/elapsed/straggling run on the injected clock, so wall-clock
    steps (NTP) cannot flag or unflag a task."""
    clock = [0.0]
    sp = StragglerPolicy(ratio=2.0, clock=lambda: clock[0])
    sp.start("t0")
    clock[0] = 1.0
    assert sp.elapsed("t0") == 1.0
    assert not sp.straggling("t0")             # no EWMA baseline yet
    assert sp.finish("t0") == "ok"             # first observation seeds EWMA
    assert sp.ewma == 1.0
    sp.start("t1")
    clock[0] = 2.5
    assert not sp.straggling("t1")             # 1.5s < 2 x 1.0
    clock[0] = 3.5
    assert sp.straggling("t1")                 # 2.5s > 2 x 1.0
    sp.start("t1")                             # duplicate dispatch keeps the
    assert sp.elapsed("t1") == 2.5             # original start stamp
    sp.abandon("t1")
    assert sp.elapsed("t1") == 0.0             # unknown after abandon
    assert not sp.straggling("t1")
    assert sp.finish("t1") == "ok"             # unknown: untracked no-op
    assert sp.ewma == 1.0


def test_straggler_finish_folds_duration_into_ewma():
    clock = [0.0]
    sp = StragglerPolicy(ratio=10.0, alpha=0.5, clock=lambda: clock[0])
    sp.start("a"); clock[0] = 2.0
    sp.finish("a")                             # seeds EWMA at 2.0
    sp.start("b"); clock[0] = 6.0
    sp.finish("b")                             # healthy: folds in 4.0
    assert sp.ewma == pytest.approx(3.0)


def test_retry_step_recovers():
    calls = []

    def flaky(x):
        calls.append(x)
        if len(calls) < 3:
            raise RuntimeError("transient")
        return x * 2

    assert retry_step(flaky, 21, retries=5) == 42
    assert len(calls) == 3
    with pytest.raises(RuntimeError):
        retry_step(lambda: (_ for _ in ()).throw(RuntimeError("x")), retries=2)


def test_retry_step_raises_first_error_of_donating_step():
    """A failed attempt of a step that donates its inputs deletes them; the
    caller must see the attempt's own error, not the deleted-buffer errors
    of the retries."""
    import jax
    import jax.numpy as jnp

    donating = jax.jit(lambda x: x * 2, donate_argnums=(0,))
    calls = []

    def step(x):
        calls.append(x)
        donating(x)
        raise FloatingPointError("loss is nan")

    errors = []
    x = jnp.ones(4)
    with pytest.raises(FloatingPointError, match="loss is nan"):
        retry_step(step, x, retries=3, on_error=lambda i, e: errors.append(e))
    assert x.is_deleted()
    assert len(calls) == 3
    assert not any(isinstance(e, FloatingPointError) for e in errors[1:])


def test_elastic_remesh_single_device():
    mesh, dropped = elastic_remesh()
    assert mesh.shape["model"] >= 1 and mesh.shape["data"] >= 1
    assert mesh.size + len(dropped) == len(__import__("jax").devices())
