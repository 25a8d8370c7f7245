"""Batched serving driver: prefill + greedy decode with a jit'd step.

The governor hook mirrors train_loop: decode is memory-bound (roofline
#Dry-run), so the governor steers toward lower frequencies between prefill
bursts — the paper's §III memory-bound downclocking opportunity.  Pass a
``governor`` (e.g. ``Governor.from_session(...)``, built on a MEASURED
latency table) plus the backend ``device`` it plans for; the hook consults
it at the prefill->decode region boundary and again after decode.  Wrap
``device`` in :class:`repro.trace.TracedBackend` and every plan decision
(with its reason) plus the issued frequency commands land in a replayable
telemetry trace.

With a :mod:`repro.obs` recorder installed, each call records a
``serve.call`` span (attributes ``batch``, ``prompt_len``, ``new_tokens``)
holding ``serve.prefill`` (dispatch and the wait on its logits), the
``serve.first_token`` event, ``serve.plan`` around each governor call, one
``serve.step`` per decode step (dispatch, eager argmax, append) and
``serve.wait`` (the wait on the last token).  With none installed each of
these costs one thread-local read.
"""
from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.registry import decode_module


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_new_tokens: int = 32
    greedy: bool = True
    seed: int = 0


# jitted once per (cfg, env, max_len), so a second serve() call with the
# same shapes runs compiled code and times serving, not compilation
@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _prefill(params, batch, cfg, env, max_len):
    return decode_module(cfg).prefill(params, batch, cfg, env, max_len)


@functools.partial(jax.jit, static_argnums=(4, 5), donate_argnums=(1,))
def _decode_step(params, cache, tok, pos, cfg, env):
    return decode_module(cfg).decode_step(params, cache, tok, pos, cfg, env)


def serve(cfg, env, params, batch, sc: ServeConfig | None = None,
          max_len: int | None = None, verbose=False,
          governor=None, device=None) -> dict:
    if sc is None:
        sc = ServeConfig()
    b, s = batch["tokens"].shape
    max_len = max_len or (s + sc.max_new_tokens)

    with obs.span("serve.call", "serve", batch=b, prompt_len=s,
                  new_tokens=sc.max_new_tokens):
        t0 = time.perf_counter()
        with obs.span("serve.prefill", "serve"):
            logits, cache = _prefill(params, batch, cfg, env, max_len)
            jax.block_until_ready(logits)
        t_prefill = time.perf_counter() - t0
        obs.event("serve.first_token", "serve")

        if governor is not None:
            from repro.dvfs.planner import Region
            # decode is memory-bound; one step costs roughly a prefill over a
            # single token, so the burst lasts ~(t_prefill / prompt_len) per
            # generated token
            per_step = max(t_prefill / max(s, 1), 1e-5)
            with obs.span("serve.plan", "serve"):
                governor.plan(Region("memory", per_step * sc.max_new_tokens),
                              device)

        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        out = [tok]
        t0 = time.perf_counter()
        for i in range(sc.max_new_tokens - 1):
            with obs.span("serve.step", "serve"):
                logits, cache = _decode_step(params, cache, tok,
                                             jnp.int32(s + i), cfg, env)
                tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
                out.append(tok)
        with obs.span("serve.wait", "serve"):
            jax.block_until_ready(tok)
        t_decode = time.perf_counter() - t0

        if governor is not None:
            from repro.dvfs.planner import Region
            # next prefill burst is compute-bound: plan back up
            with obs.span("serve.plan", "serve"):
                governor.plan(Region("compute", max(t_prefill, 1e-3)), device)

        tokens = jnp.concatenate(out, axis=1)
    return {
        "tokens": tokens,
        "logits": logits,              # of the last generated token
        "prefill_s": t_prefill,
        "decode_s": t_decode,
        "tokens_per_s": (b * (sc.max_new_tokens - 1)) / max(t_decode, 1e-9),
    }
