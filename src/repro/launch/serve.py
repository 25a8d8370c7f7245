"""Serving launcher:  PYTHONPATH=src python -m repro.launch.serve \
    --arch qwen3-32b --smoke --batch 4 --new-tokens 16"""
from __future__ import annotations

import argparse

import jax

from repro.configs import ARCH_IDS, get_config
from repro.configs.registry import model_module
from repro.configs.shapes import ShapeSpec
from repro.data.synthetic import make_batch
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_smoke_mesh
from repro.parallel.sharding import make_env
from repro.runtime.serve_loop import ServeConfig, serve


def main(argv=None) -> dict:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen3-32b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, smoke=args.smoke)
    env = make_env(cfg, make_smoke_mesh() if args.smoke else None)
    mod = model_module(cfg)
    params, _ = mod.init(jax.random.PRNGKey(0), cfg)
    shape = ShapeSpec("cli", args.seq, args.batch, "prefill")
    batch = make_batch(cfg, shape)
    res = serve(cfg, env, params, batch,
                ServeConfig(max_new_tokens=args.new_tokens))
    print(f"prefill {res['prefill_s']*1e3:.0f} ms, "
          f"decode {res['tokens_per_s']:.1f} tok/s, "
          f"first row: {res['tokens'][0][:8].tolist()}")
    return res


if __name__ == "__main__":
    main()
