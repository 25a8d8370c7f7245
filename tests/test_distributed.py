"""Real multi-device SPMD correctness: runs a subprocess with 8 host
devices (XLA_FLAGS) and checks that sharded execution is numerically
equivalent to single-device execution for the core paths
(:func:`repro.parallel.equivalence.check_sharded_equivalence` on a (2,4)
("data","model") mesh — ``chip_smoke.py --chips 4`` runs the same checks
on four chips):

  * train step == unsharded step
  * flash-decoding (seq-sharded KV, shard_map LSE combine) == plain decode
  * shard_map expert-parallel MoE == local dispatch

The SAME code paths the 512-chip dry-run compiles are executed and checked
for value equality.
"""
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
from repro.launch.mesh import make_mesh
from repro.parallel.equivalence import check_sharded_equivalence

check_sharded_equivalence(make_mesh((2, 4), ("data", "model")))
print("ALL-OK")
"""


@pytest.mark.slow
def test_multi_device_equivalence():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-3000:]
    assert "ALL-OK" in res.stdout


_ELASTIC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.configs.shapes import ShapeSpec
from repro.launch.mesh import make_mesh
from repro.parallel.sharding import make_env
from repro.runtime.train_loop import TrainConfig, train
import tempfile, dataclasses

cfg = get_config("llama3-8b", smoke=True)
cfg = dataclasses.replace(cfg, param_dtype=jnp.float32,
                          compute_dtype=jnp.float32)
shape = ShapeSpec("t", 16, 8, "train")

# straight 6-step single-device run = the reference
env0 = make_env(cfg, None)
ref = train(cfg, shape, env0, TrainConfig(steps=6, log_every=100),
            verbose=False)

with tempfile.TemporaryDirectory() as d:
    # 3 steps on a (2,4) mesh, checkpoint...
    mesh_a = make_mesh((2, 4), ("data", "model"))
    env_a = make_env(cfg, mesh_a)
    train(cfg, shape, env_a, TrainConfig(steps=3, checkpoint_every=3,
                                         checkpoint_dir=d, log_every=100),
          verbose=False)
    # ...then ELASTIC RESCALE: resume on a (4,2) mesh (pod loss scenario)
    mesh_b = make_mesh((4, 2), ("data", "model"))
    env_b = make_env(cfg, mesh_b)
    out = train(cfg, shape, env_b, TrainConfig(steps=6, checkpoint_every=100,
                                               checkpoint_dir=d,
                                               log_every=100), verbose=False)
assert out["resumed_at"] == 3, out["resumed_at"]
diff = abs(ref["loss"][-1] - out["loss"][-1])
assert diff < 5e-3, (ref["loss"][-1], out["loss"][-1])
print("ELASTIC-OK", ref["loss"][-1], out["loss"][-1])
"""


@pytest.mark.slow
def test_elastic_rescale_resume():
    """Train on a (2,4) mesh, checkpoint, resume on a (4,2) mesh (pod-loss
    rescale); final loss matches the uninterrupted single-device run."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "-c", _ELASTIC_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-3000:]
    assert "ELASTIC-OK" in res.stdout
