"""End-to-end behaviour tests: train-to-convergence on the synthetic
grammar, serving, and the full paper pipeline feeding the governor."""
import jax
import numpy as np

from repro.configs import get_config
from repro.configs.registry import model_module
from repro.configs.shapes import ShapeSpec
from repro.data.synthetic import SyntheticTokens, make_batch
from repro.parallel.sharding import make_env
from repro.runtime.serve_loop import ServeConfig, serve
from repro.runtime.train_loop import TrainConfig, train

ENV = make_env(None, None)


def test_train_loss_decreases():
    cfg = get_config("llama3-8b", smoke=True)
    shape = ShapeSpec("t", 32, 4, "train")
    m = train(cfg, shape, ENV, TrainConfig(steps=60, lr=2e-3,
                                           log_every=100), verbose=False)
    first = np.mean(m["loss"][:5])
    last = np.mean(m["loss"][-5:])
    assert last < first - 0.15, (first, last)   # learns the markov grammar


def test_data_pipeline_deterministic():
    ds = SyntheticTokens(vocab=128, seq_len=16, global_batch=4, seed=3)
    a = ds.batch_at(7)["tokens"]
    b = ds.batch_at(7)["tokens"]
    c = ds.batch_at(8)["tokens"]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))


def test_serve_end_to_end():
    cfg = get_config("qwen3-32b", smoke=True)
    mod = model_module(cfg)
    params, _ = mod.init(jax.random.PRNGKey(0), cfg)
    shape = ShapeSpec("s", 16, 2, "prefill")
    batch = make_batch(cfg, shape)
    res = serve(cfg, ENV, params, batch, ServeConfig(max_new_tokens=8))
    assert res["tokens"].shape == (2, 8)
    assert int(res["tokens"].max()) < cfg.vocab
    assert res["tokens_per_s"] > 0


def test_paper_pipeline_feeds_governor():
    """Measure a simulated device -> latency table -> governor plans an
    energy-aware schedule for a real dry-run cell's region profile."""
    import glob
    import json

    from repro.core.evaluation import MeasureConfig
    from repro.core.latest import LatestConfig, run_latest
    from repro.dvfs import PowerModel, make_device
    from repro.dvfs.governor import Governor, static_sim
    from repro.dvfs.planner import regions_from_cell

    dev = make_device("a100", seed=0, n_cores=8)
    freqs = [210.0, 705.0, 1095.0, 1410.0]
    table = run_latest(dev, freqs, LatestConfig(
        measure=MeasureConfig(min_measurements=4, max_measurements=4)))
    assert len(table.pairs) >= 6

    from repro.core.paths import results_dir
    cells = glob.glob(results_dir("dryrun", "*train_4k__single.json"))
    regions = None
    if cells:                                    # use the real roofline cell
        cell = json.load(open(cells[0]))
        if cell["status"] == "ok":
            regions = regions_from_cell(cell)
    if regions is None:
        from repro.dvfs.planner import Region
        regions = [Region("compute", 0.3), Region("collective", 0.1)]

    power = PowerModel(f_max_mhz=1410.0)
    g = Governor(table, power, freqs)
    stats = g.simulate(regions * 50)
    base = static_sim(power, freqs, regions * 50)
    assert stats.energy_j <= base.energy_j       # never worse than static
    assert stats.time_s <= 1.1 * base.time_s
