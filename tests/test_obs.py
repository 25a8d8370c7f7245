"""Span profiler (`repro.obs`): recorder semantics, span-tree invariants
(deterministic + hypothesis property forms), the 3-node chaos-run merge,
dominant-cost naming for the straggler and retry-storm scenarios, the
spans-on/off store bit-identity gate, Perfetto export validation, the
metrics bridge, and the `campaign profile` / `--json` CLI surface."""
import json
import os

import numpy as np
import pytest

from repro import obs
from repro.campaign import (ArtifactStore, CampaignRunner, CampaignSpec,
                            DeviceSpec, MeasureSpec, run_campaign)
from repro.campaign.cluster.retry import RetryPolicy
from repro.campaign.workqueue import FaultPlan
from repro.obs import (SpanRecorder, analyze, build_forest, critical_path,
                       export_to_registry, load_span_rows, self_time,
                       to_trace_events, validate_trace_events, walk)
from repro.obs.profile import (collect_span_rows, profile_campaign,
                               profile_markdown)

FAST = MeasureSpec(key="fast", min_measurements=4, max_measurements=5,
                   rse_check_every=4)
FREQS = (210.0, 705.0, 1410.0)


def _device(key, seed, kind="a100"):
    return DeviceSpec.make(key, "simulated",
                           {"kind": kind, "n_cores": 6, "seed": seed},
                           frequencies=FREQS)


def _fleet(n=3, retries=3, name="obs"):
    return CampaignSpec(name, devices=tuple(_device(f"u{i}", i)
                                            for i in range(n)),
                        measures=(FAST,), retries=retries)


def _assert_store_bit_identical(ref, cand):
    """Spans must never perturb measurement bits: whole-campaign digest
    equality plus array-level table equality."""
    assert ref.campaign.content_digest() == cand.campaign.content_digest()
    assert set(ref.outcomes) == set(cand.outcomes)
    for key in ref.outcomes:
        rt, ct = ref.campaign.load_table(key), cand.campaign.load_table(key)
        assert set(rt.pairs) == set(ct.pairs)
        for p, pr in rt.pairs.items():
            assert np.array_equal(pr.latencies, ct.pairs[p].latencies)
            assert np.array_equal(pr.outlier_mask, ct.pairs[p].outlier_mask)


@pytest.fixture(autouse=True)
def _no_leaked_recorder():
    yield
    obs.uninstall()
    obs.uninstall(thread_only=True)


# ------------------------------------------------------------------ #
# recorder + ambient API
# ------------------------------------------------------------------ #
def _fake_clock(start=100.0, step=0.5):
    t = [start - step]

    def clock():
        t[0] += step
        return t[0]
    return clock


def test_recorder_rows_schema_and_nesting(tmp_path):
    path = str(tmp_path / "a.jsonl")
    rec = SpanRecorder("driver", path=path, clock=_fake_clock())
    with rec.span("campaign.run", "campaign", campaign_id="c1"):
        with rec.span("unit.attempt", "unit", unit="u0") as live:
            assert live.attrs == {"unit": "u0"}
            live.attrs["status"] = "done"    # mutable while open
            rec.event("sched.requeue", "sched", unit="u0")
    rec.close()
    rows = load_span_rows(path)
    assert [r["name"] for r in rows] == ["sched.requeue", "unit.attempt",
                                        "campaign.run"]
    by_name = {r["name"]: r for r in rows}
    root = by_name["campaign.run"]
    child = by_name["unit.attempt"]
    ev = by_name["sched.requeue"]
    assert root["parent"] is None and root["actor"] == "driver"
    assert child["parent"] == root["sid"]        # ambient stack nesting
    assert ev["parent"] == child["sid"] and ev["ph"] == "i"
    assert ev["t0"] == ev["t1"]
    assert child["attrs"] == {"unit": "u0", "status": "done"}
    assert child["t1"] > child["t0"]
    assert all(r["sid"].startswith("driver:") for r in rows)
    assert len({r["sid"] for r in rows}) == 3


def test_begin_end_spans_do_not_touch_the_ambient_stack():
    rec = SpanRecorder("d", clock=_fake_clock())
    with rec.span("outer", "campaign"):
        live = rec.begin("attempt", "unit", unit="u1")
        assert rec.ctx() != live.sid             # stack still on "outer"
        rec.end(live, status="requeued")
    rows = rec.rows()
    attempt = [r for r in rows if r["name"] == "attempt"][0]
    assert attempt["attrs"]["status"] == "requeued"
    assert attempt["parent"] == [r for r in rows
                                 if r["name"] == "outer"][0]["sid"]


def test_load_span_rows_tolerates_torn_tail(tmp_path):
    path = str(tmp_path / "torn.jsonl")
    rec = SpanRecorder("n", path=path, clock=_fake_clock())
    with rec.span("ok", "exec"):
        pass
    rec.close()
    with open(path, "a") as f:
        f.write('{"sid": "n:99", "name": "torn')   # crash mid-append
    rows = load_span_rows(path)
    assert [r["name"] for r in rows] == ["ok"]


def test_ambient_api_is_noop_when_off():
    assert not obs.enabled()
    assert obs.ctx() is None
    assert obs.event("x", "y") is None
    cm = obs.span("x", "y")
    with cm as live:
        assert live is None
    assert obs.span("z", "w") is cm              # shared no-op, no alloc


def test_plain_recorder_imports_no_jax():
    """Campaign workers record spans without JAX: only ``annotate=True``
    imports it."""
    import subprocess
    import sys

    code = ("import sys\n"
            "from repro import obs\n"
            "rec = obs.install(obs.SpanRecorder('worker'))\n"
            "with obs.span('unit.exec', 'exec'):\n"
            "    obs.event('sched.requeue', 'sched')\n"
            "assert len(rec.rows()) == 2\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), os.pardir, "src"),
         env.get("PYTHONPATH", "")])
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr


def test_thread_local_recorder_shadows_process_default_and_suppressed():
    proc = obs.install(SpanRecorder("proc", clock=_fake_clock()))
    local = SpanRecorder("node", clock=_fake_clock())
    assert obs.current() is proc
    obs.install(local, thread_only=True)
    assert obs.current() is local
    with obs.suppressed():
        assert obs.current() is None and not obs.enabled()
    assert obs.current() is local
    obs.uninstall(thread_only=True)
    assert obs.current() is proc


def test_span_records_exception_as_error_attr():
    rec = obs.install(SpanRecorder("d", clock=_fake_clock()))
    with pytest.raises(RuntimeError):
        with obs.span("boom", "exec"):
            raise RuntimeError("nope")
    row = rec.rows()[0]
    assert row["attrs"]["error"] == "RuntimeError"


def test_governor_plan_emits_linked_event():
    from repro.core.latency_table import LatencyTable, analyse_pair
    from repro.dvfs.governor import Governor
    from repro.dvfs.planner import Region
    from repro.dvfs.power_model import PowerModel
    rng = np.random.default_rng(0)
    table = LatencyTable()
    for fi in (500.0, 2000.0):
        for ft in (500.0, 2000.0):
            if fi != ft:
                table.add(analyse_pair(fi, ft,
                                       0.01 * rng.lognormal(0, 0.03, 30)))
    rec = obs.install(SpanRecorder("d", clock=_fake_clock()))
    g = Governor(table, PowerModel(2000.0), [500.0, 2000.0])
    g.plan(Region("memory", 5.0))
    events = [r for r in rec.rows() if r["name"] == "gov.plan"]
    assert len(events) == 1
    attrs = events[0]["attrs"]
    assert {"f_from", "f_to", "reason"} <= set(attrs)
    assert "audit" in attrs                      # None without a traced
    assert attrs["audit"] is None                # backend, but always linked


# ------------------------------------------------------------------ #
# span-tree invariants: deterministic + hypothesis property forms
# ------------------------------------------------------------------ #
def _row(sid, parent, t0, t1, name="s", cat="x", ph="X"):
    return {"sid": sid, "parent": parent, "actor": sid.split(":")[0],
            "name": name, "cat": cat, "ph": ph, "tid": 0,
            "t0": float(t0), "t1": float(t1)}


def _rows_from_plan(plan):
    """(parent_pick, start_frac, dur_frac) triples -> a span forest with
    one fixed root; child intervals may spill outside their parent so the
    clamp path is always exercised."""
    rows = [_row("a:1", None, 0.0, 100.0, name="root", cat="campaign")]
    for i, (pick, f0, f1) in enumerate(plan, start=2):
        parent = rows[pick % len(rows)]
        t0 = -5.0 + f0 * 110.0
        rows.append(_row(f"a:{i}", parent["sid"], t0, t0 + f1 * 40.0))
    return rows


def _assert_tree_invariants(rows):
    roots = build_forest(rows)
    for root in roots:
        for n in walk(root):
            for c in n.children:
                # children clamped into their parent, never inverted
                assert c.t0 >= n.t0 - 1e-9 and c.t1 <= n.t1 + 1e-9
                assert c.t1 >= c.t0
            assert self_time(n) >= 0.0
        segments = critical_path(root)
        total = sum(s.duration for s in segments)
        # the critical path tiles the root exactly: it can never exceed
        # the tree's wall time, and for a single root it equals it
        assert total <= root.duration + 1e-6
        assert abs(total - root.duration) < 1e-6
        if segments:
            assert abs(segments[0].t0 - root.t0) < 1e-9
            assert abs(segments[-1].t1 - root.t1) < 1e-9
            for a, b in zip(segments, segments[1:]):
                assert abs(a.t1 - b.t0) < 1e-9   # contiguous, no overlap
        # every instant is attributed to >= 1 span, so self times can
        # only meet or exceed the root wall (equality when disjoint)
        assert sum(self_time(n) for n in walk(root)) >= root.duration - 1e-6


def test_forest_invariants_on_seeded_random_trees():
    for seed in range(25):
        rng = np.random.default_rng(seed)
        n = int(rng.integers(0, 40))
        plan = [(int(rng.integers(0, 1000)), float(rng.random()),
                 float(rng.random())) for _ in range(n)]
        _assert_tree_invariants(_rows_from_plan(plan))


def test_self_time_sums_to_root_wall_for_disjoint_children():
    for seed in range(25):
        rng = np.random.default_rng(100 + seed)
        rows = []
        counter = [0]

        def build(parent, t0, t1, depth):
            counter[0] += 1
            sid = f"a:{counter[0]}"
            rows.append(_row(sid, parent, t0, t1))
            if depth < 3 and t1 > t0:
                k = int(rng.integers(0, 4))
                if k:
                    cuts = sorted(rng.uniform(t0, t1, 2 * k))
                    for j in range(k):
                        build(sid, cuts[2 * j], cuts[2 * j + 1], depth + 1)

        build(None, 0.0, 100.0, 0)
        (root,) = build_forest(rows)
        total_self = sum(self_time(n) for n in walk(root))
        assert total_self == pytest.approx(root.duration, abs=1e-6)
        crit = sum(s.duration for s in critical_path(root))
        assert crit == pytest.approx(root.duration, abs=1e-6)


def test_prop_forest_invariants_hold_for_arbitrary_plans():
    pytest.importorskip("hypothesis")  # property tests run when installed
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=80, deadline=None)
    @given(st.lists(st.tuples(st.integers(0, 10 ** 6),
                              st.floats(0.0, 1.0), st.floats(0.0, 1.0)),
                    max_size=32))
    def check(plan):
        _assert_tree_invariants(_rows_from_plan(plan))

    check()


def test_prop_critical_path_never_exceeds_any_root():
    pytest.importorskip("hypothesis")  # property tests run when installed
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=60, deadline=None)
    @given(st.lists(st.tuples(st.floats(0.0, 1.0), st.floats(0.0, 1.0)),
                    min_size=1, max_size=24))
    def check(spans):
        # a forest of detached roots (lost parent files): each analyzed
        # root's critical path is bounded by its own wall time
        rows = [_row(f"a:{i + 1}", f"ghost:{i}", 100.0 * f0,
                     100.0 * f0 + 50.0 * f1) for i, (f0, f1)
                in enumerate(spans)]
        for root in build_forest(rows):
            total = sum(s.duration for s in critical_path(root))
            assert total <= root.duration + 1e-6

    check()


def test_analyze_orphan_rows_become_roots_behind_the_campaign_root():
    rows = [
        _row("d:1", None, 0.0, 10.0, name="campaign.run", cat="campaign"),
        _row("d:2", "d:1", 1.0, 9.0, name="unit.attempt", cat="unit"),
        _row("n:1", "lost:7", 2.0, 8.0, name="unit.exec", cat="exec"),
    ]
    doc = analyze(build_forest(rows))
    assert doc["root"]["name"] == "campaign.run"   # longest root wins
    assert doc["spans"] == 3


# ------------------------------------------------------------------ #
# metrics bridge + Perfetto export (synthetic rows)
# ------------------------------------------------------------------ #
def test_bridge_maps_events_to_counters_and_queue_gauges():
    rows = [
        _row("d:1", None, 0.0, 2.0, name="campaign.run", cat="campaign"),
        _row("d:2", "d:1", 0.1, 1.0, name="store.mark", cat="store"),
        _row("d:3", "d:1", 0.2, 0.2, name="sched.requeue", cat="sched",
             ph="i"),
        _row("d:4", "d:1", 0.3, 0.3, name="store.retry", cat="store",
             ph="i"),
        _row("d:5", "d:1", 0.4, 0.4, name="msg.send", cat="msg", ph="i"),
        _row("d:6", "d:1", 0.5, 0.5, name="msg.recv", cat="msg", ph="i"),
        _row("d:7", "d:1", 0.6, 0.6, name="gov.plan", cat="gov", ph="i"),
    ]
    rows[2]["attrs"] = {"queue": 3}
    reg = export_to_registry(rows)
    snap = reg.snapshot()
    assert snap["obs_requeued_units_total"][""] == 1
    assert snap["obs_store_retries_total"][""] == 1
    assert snap["obs_governor_plans_total"][""] == 1
    assert snap["obs_msgs_total"]['{direction="send"}'] == 1
    assert snap["obs_msgs_total"]['{direction="recv"}'] == 1
    assert snap["obs_spans_total"]['{cat="campaign"}'] == 1
    assert snap["obs_spans_total"]['{cat="store"}'] == 1
    assert snap["obs_events_total"]['{name="gov.plan"}'] == 1
    assert snap["obs_queue_depth_peak"][""] == 3.0
    hist = snap["obs_stage_seconds"]['{cat="store"}']
    assert hist["count"] == 1 and hist["sum"] == pytest.approx(0.9)
    # idempotent folding into an existing registry accumulates
    reg2 = export_to_registry(rows, registry=reg)
    assert reg2 is reg
    assert reg.snapshot()["obs_store_retries_total"][""] == 2


def test_trace_event_export_schema_and_relative_timestamps():
    rows = [
        _row("d:1", None, 50.0, 60.0, name="campaign.run", cat="campaign"),
        _row("n:1", "d:1", 51.0, 59.0, name="unit.exec", cat="exec"),
        _row("n:2", "n:1", 52.0, 52.0, name="store.retry", cat="store",
             ph="i"),
    ]
    doc = to_trace_events(rows)
    assert validate_trace_events(doc) == []
    events = doc["traceEvents"]
    metas = [e for e in events if e["ph"] == "M"]
    assert {m["args"]["name"] for m in metas} == {"repro/d", "repro/n"}
    xs = [e for e in events if e["ph"] == "X"]
    assert min(e["ts"] for e in xs) == 0.0       # rebased to the earliest
    exec_ev = [e for e in xs if e["name"] == "unit.exec"][0]
    assert exec_ev["dur"] == pytest.approx(8e6)
    assert exec_ev["args"]["parent"] == "d:1"
    assert validate_trace_events({"traceEvents": []})
    assert validate_trace_events({"traceEvents": [{"ph": "Q"}]})


# ------------------------------------------------------------------ #
# end-to-end: chaos-run merge, bit-identity, dominant-cost naming
# ------------------------------------------------------------------ #
def test_serial_campaign_bit_identical_with_spans_on(tmp_path):
    spec = _fleet(2)
    ref = run_campaign(spec, ArtifactStore(str(tmp_path / "off")))
    assert ref.ok
    cand = CampaignRunner(spec, ArtifactStore(str(tmp_path / "on")),
                          spans=True).run()
    assert cand.ok
    _assert_store_bit_identical(ref, cand)
    assert not ref.campaign.list_span_files()
    files = cand.campaign.list_span_files()
    assert [os.path.basename(p) for p in files] == ["driver.jsonl"]
    rows = collect_span_rows(cand.campaign)
    assert validate_trace_events(to_trace_events(rows)) == []
    doc = analyze(build_forest(rows))
    assert doc["root"]["name"] == "campaign.run"
    # per-pair spans from the measurement session made it into the tree
    assert doc["spans"] > 2 * len(FREQS) * (len(FREQS) - 1)


def test_three_node_chaos_run_merges_into_one_consistent_tree(tmp_path):
    """Node crash + lossy/dup/delayed transport + transient store faults,
    spans on: the store stays bit-identical to a clean serial run, every
    cross-actor parent link resolves in the merged rows, and the requeue
    shows up in the profiled event counters."""
    spec = _fleet(3)
    ref = run_campaign(spec, ArtifactStore(str(tmp_path / "serial")))
    assert ref.ok
    plan = FaultPlan.make(
        node_crash_after_pairs={"u0@fast": 1},
        transport={"drop_rate": 0.05, "dup_rate": 0.05,
                   "delay_s": 0.001, "seed": 7},
        store_transient={"u1@fast": 2})
    cand = CampaignRunner(
        spec, ArtifactStore(str(tmp_path / "chaos")), executor="cluster",
        max_workers=3, heartbeat_timeout_s=5.0, fault_plan=plan,
        spans=True).run()
    assert cand.ok, [(o.key, o.error) for o in cand.failed()]
    assert cand.stats.get("crashed_nodes", 0) >= 1
    _assert_store_bit_identical(ref, cand)

    files = {os.path.basename(p) for p in cand.campaign.list_span_files()}
    assert "driver.jsonl" in files
    assert sum(1 for f in files if f.startswith("node-")) >= 2

    rows = collect_span_rows(cand.campaign)
    sids = {r["sid"] for r in rows}
    orphans = [r for r in rows if r.get("parent") and
               r["parent"] not in sids]
    assert orphans == [], (
        "cross-actor parent links must resolve in the merged rows: "
        + str([(r['sid'], r['parent']) for r in orphans]))
    doc = analyze(build_forest(rows))
    assert doc["root"]["name"] == "campaign.run"
    assert {"driver"} < set(doc["actors"])       # driver + node actors
    assert doc["event_counts"].get("sched.requeue", 0) >= 1
    assert doc["event_counts"].get("store.retry", 0) >= 1
    assert doc["critical_path"]["total_s"] == pytest.approx(
        doc["root"]["wall_s"], rel=1e-6)
    assert validate_trace_events(to_trace_events(rows)) == []


def test_profile_names_the_straggler_as_dominant_cost(tmp_path):
    spec = _fleet(3)
    cand = CampaignRunner(
        spec, ArtifactStore(str(tmp_path / "straggler")),
        executor="cluster", max_workers=3, heartbeat_timeout_s=5.0,
        fault_plan=FaultPlan.make(slow_pairs_s={"u0@fast": 0.15}),
        spans=True).run()
    assert cand.ok, [(o.key, o.error) for o in cand.failed()]
    doc = profile_campaign(cand.campaign)
    dom = doc["dominant"]
    assert dom is not None
    assert dom["label"].startswith("straggler unit u0@fast"), dom["label"]
    assert dom["span"]["unit"] == "u0@fast"
    assert dom["frac"] > 0.3
    md = profile_markdown(doc)
    assert "straggler unit u0@fast" in md


def test_profile_names_the_retry_storm_as_dominant_cost(tmp_path):
    spec = _fleet(2)
    cand = CampaignRunner(
        spec, ArtifactStore(str(tmp_path / "storm")), executor="cluster",
        max_workers=2, heartbeat_timeout_s=5.0,
        retry_policy=RetryPolicy(max_attempts=8, base_s=0.08, cap_s=0.3,
                                 timeout_s=5.0),
        fault_plan=FaultPlan.make(store_transient={"u0@fast": 12}),
        spans=True).run()
    assert cand.ok, [(o.key, o.error) for o in cand.failed()]
    doc = profile_campaign(cand.campaign)
    dom = doc["dominant"]
    assert dom is not None
    assert dom["label"].startswith(
        "remote-store retries / partition healing"), dom["label"]
    assert doc["event_counts"].get("store.retry", 0) >= 12
    # the backoff waits sit inside store spans, so retries dominate
    assert dom["frac"] > 0.4


def test_dead_letters_carry_span_context_into_the_profile(tmp_path):
    spec = _fleet(2)
    cand = CampaignRunner(
        spec, ArtifactStore(str(tmp_path / "dl")), executor="cluster",
        max_workers=2, heartbeat_timeout_s=5.0,
        fault_plan=FaultPlan.make(store_permanent=("u0@fast",)),
        spans=True).run()
    assert not cand.ok                     # the poisoned unit failed ...
    assert "u1@fast" in {o.key for o in cand.outcomes.values()
                         if o.status == "done"}   # ... alone
    doc = profile_campaign(cand.campaign)
    letters = doc["dead_letters"]
    assert letters, "exhausted retries must be dead-lettered"
    linked = [dl for dl in letters if dl["span"]]
    assert linked, "dead letters must carry the active span id"
    for dl in linked:
        assert dl["elapsed_s"] is not None and dl["elapsed_s"] >= 0.0
        assert dl["attempts"] >= 1
        assert isinstance(dl["on_critical_path"], bool)
    md = profile_markdown(doc)
    assert "Dead letters" in md


# ------------------------------------------------------------------ #
# CLI surface: profile + the --json listing/report satellites
# ------------------------------------------------------------------ #
def _write_spec(tmp_path, spec):
    path = str(tmp_path / "spec.json")
    with open(path, "w") as f:
        json.dump(spec.to_dict(), f)
    return path


def test_cli_profile_and_json_surfaces(tmp_path, capsys):
    from repro.campaign.cli import main
    spec = _fleet(1, name="obs-cli")
    spec_path = _write_spec(tmp_path, spec)
    root = str(tmp_path / "store")

    assert main(["--store", root, "run", spec_path, "--quiet"]) == 0
    capsys.readouterr()
    cid = spec.campaign_id()

    # no spans recorded yet: profile exits 1 and says how to fix it
    assert main(["--store", root, "profile", cid]) == 1
    assert "--spans" in capsys.readouterr().out

    # resume the same campaign with spans on, then profile it
    assert main(["--store", root, "run", spec_path, "--quiet",
                 "--spans"]) == 0
    capsys.readouterr()
    perfetto = str(tmp_path / "trace.json")
    metrics = str(tmp_path / "metrics.json")
    assert main(["--store", root, "profile", cid, "--perfetto", perfetto,
                 "--metrics-out", metrics]) == 0
    out = capsys.readouterr().out
    assert "# Campaign profile" in out and "Dominant cost" in out
    with open(perfetto) as f:
        assert validate_trace_events(json.load(f)) == []
    with open(metrics) as f:
        names = set(json.load(f))
    assert "obs_spans_total" in names and "obs_stage_seconds" in names

    assert main(["--store", root, "profile", cid, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["campaign_id"] == cid
    assert doc["root"]["name"] == "campaign.run"
    assert doc["span_files"] == ["driver.jsonl"]

    assert main(["--store", root, "ls", "--json"]) == 0
    listing = json.loads(capsys.readouterr().out)
    assert [d["campaign_id"] for d in listing] == [cid]
    assert listing[0]["span_files"] == 1
    assert listing[0]["units_done"] == 1

    assert main(["--store", root, "report", "--json", cid]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["campaign_id"] == cid
    assert report["units_done"] == report["units_total"] == 1
    assert {r["unit"] for r in report["comparison"]} == {"u0@fast"}
    assert "asymmetry" in report

    out_path = str(tmp_path / "profile.md")
    assert main(["--store", root, "profile", cid, "--out", out_path]) == 0
    with open(out_path) as f:
        assert "Dominant cost" in f.read()
