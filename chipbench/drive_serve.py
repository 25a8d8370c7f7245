"""Serving cells: one client sends batches of greedy requests through
``repro.runtime.serve_loop.serve`` and waits for each (a closed loop).

A request is due when its ``serve()`` call starts.  Its first token is
there when ``serve()`` makes its first governor call, which it makes right
after the prefill's logits are ready; the proxy in ``harness.PlanTimer``
stamps that moment on the host clock.  The request completes when
``serve()`` returns, after the last token is ready.

``correct`` compares the served tokens of a sample of finished requests,
drawn from the seed with the longest prompt among them, with the plain
reference run over each prompt and its served tokens: the widest gap by
which a served token's reference logit lies below the reference's best.
"""
from __future__ import annotations

import dataclasses
import gc

import jax
import jax.numpy as jnp
import numpy as np
from repro.parallel.sharding import make_env
from repro.runtime import serve_loop

import generator
import harness
import refops
from harness import now


@dataclasses.dataclass
class Batch:
    index: int
    prompt_len: int
    size: int
    new_tokens: int
    due: float
    first: float
    done: float


class Session:
    """Weights, governor and warmed programs of one serving cell."""

    def __init__(self, cell: harness.Cell, seed: int, spans: harness.Spans):
        self.cell, self.seed, self.spans = cell, seed, spans
        self.cfg = harness.program_config(cell.spec)
        self.env = make_env(self.cfg, None)
        self.gen = generator.make(cell.traffic, cell.spec["vocab"], seed)
        self.key = jax.random.PRNGKey(harness.init_seed(seed))
        # One jitted call draws every weight; lowering, compiling (or
        # loading from the compile cache) and drawing are timed apart.
        t = now()
        lowered = jax.jit(
            lambda k: cell.cmod.init_params(k, cell.spec)).lower(self.key)
        t1 = now()
        self.init = lowered.compile()
        t2 = now()
        self.params = jax.block_until_ready(self.init(self.key))
        t3 = now()
        self.parts = {"weights": {"lower_s": t1 - t, "compile_s": t2 - t1,
                                  "draw_s": t3 - t2}}
        t = now()
        gov, self.gdev = harness.build_serve_governor(cell.traffic["governor"])
        self.parts["governor_s"] = now() - t
        self.planner = harness.PlanTimer(gov, spans)
        self.gc_clock = harness.GcClock()
        self.sc = serve_loop.ServeConfig(
            max_new_tokens=cell.traffic["new_tokens"])
        t = now()
        for length in self.gen.lengths:
            out = self._call(self.gen.warmup_tokens(length))
            np.asarray(out["tokens"])
        self.parts["warmup_s"] = now() - t
        spans.records.clear()

    def _call(self, tokens):
        return serve_loop.serve(self.cfg, self.env, self.params,
                                {"tokens": jnp.asarray(tokens)}, self.sc,
                                governor=self.planner, device=self.gdev)

    def window(self, seconds: float):
        """Batches until ``seconds`` have passed; returns (batches, served
        token arrays on the device)."""
        batches, served = [], []
        t_start = now()
        i = 0
        with self.spans("cb.window"), self.gc_clock:
            while True:
                tokens = self.gen.batch_tokens(i)
                k = len(self.spans.records)
                due = now()
                with self.spans("cb.serve"):
                    out = self._call(tokens)
                done = now()
                first = next(a for n, a, _ in self.spans.records[k:]
                             if n == "cb.plan")
                batches.append(Batch(i, tokens.shape[1], tokens.shape[0],
                                     self.sc.max_new_tokens, due, first, done))
                served.append(out["tokens"])
                i += 1
                if done - t_start >= seconds:
                    break
        return batches, served, (t_start, batches[-1].done)

    def free(self):
        del self.params
        gc.collect()


def tpot_p95_ms(batches) -> float:
    """p95 over all requests of (done - first token) / (new tokens - 1)."""
    return float(np.percentile(
        [1e3 * (b.done - b.first) / (b.new_tokens - 1)
         for b in batches for _ in range(b.size)], 95))


def end_to_end(batches, t0, t1, setup_s) -> dict:
    tokens = sum(b.size * b.new_tokens for b in batches)
    ttft = [1e3 * (b.first - b.due) for b in batches for _ in range(b.size)]
    return {"serve_tokens_per_s": tokens / (t1 - t0),
            "ttft_p95_ms": float(np.percentile(ttft, 95)),
            "tpot_p95_ms": tpot_p95_ms(batches),
            "setup_s": setup_s}


def sample(sess: Session, batches, served):
    """[(prompt ids, served ids)] of the sampled requests, on the host."""
    picks = sess.gen.sample([b.index for b in batches],
                            sess.cell.traffic["sample_requests"])
    return [(sess.gen.batch_tokens(b)[r], np.asarray(served[b][r]))
            for b, r in picks]


def widest_gap(cmod, spec, params, reqs, q=None):
    """Widest gap below the reference's best logit of the tokens served
    (``q`` None), or of the tokens a ``q``-rounded reference puts first."""
    widest = 0.0
    for prompt, out in reqs:
        full = jnp.asarray(np.concatenate([prompt, out[:-1]]))
        n = len(prompt) - 1
        ref = cmod.logits(params, full, spec)[n:]
        chosen = jnp.asarray(out) if q is None else jnp.argmax(
            cmod.logits(params, full, spec, refops.QUANTIZERS[q])[n:], -1)
        gap = jnp.max(ref, -1) - jnp.take_along_axis(ref, chosen[:, None], -1)[:, 0]
        widest = max(widest, float(jnp.max(gap)))
    return widest


def run(cell, seed, seconds, trace, t0, devices):
    spans = harness.Spans()
    start_s = now() - t0
    sess = Session(cell, seed, spans)
    setup_s = now() - t0
    prof = harness.Profiler(trace)
    prof.start()
    batches, served, (w0, w1) = sess.window(
        min(seconds, cell.traffic["trace_seconds"]) if trace else seconds)
    prof.stop()
    device = harness.device_info(devices)

    vocab = cell.spec["vocab"]
    valid = all(bool(((np.asarray(s) >= 0) & (np.asarray(s) < vocab)).all())
                for s in served)
    reqs = sample(sess, batches, served)
    del served
    sess.free()
    t_ref = now()
    params = sess.init(sess.key)
    gap = widest_gap(cell.cmod, cell.spec, params, reqs)
    del params
    t_ref = now() - t_ref
    checks = {"max_logit_gap": {"value": gap,
                                "limit": cell.limits["max_logit_gap"]}}
    correct = valid and gap <= cell.limits["max_logit_gap"]

    attempted = sum(b.size for b in batches)
    batch_s = [b.done - b.due for b in batches]
    slowest = sorted(range(len(batches)), key=batch_s.__getitem__)[-3:]
    timing = dict(start_s=start_s, **sess.parts, setup_s=setup_s,
                  reference_s=t_ref)
    timing["batch_s"] = {"min": min(batch_s), "median": float(np.median(batch_s)),
                         "max": max(batch_s),
                         "slowest": [[batches[j].index, batches[j].prompt_len,
                                      batch_s[j]] for j in reversed(slowest)]}
    timing["window_gc"] = {"seconds": sess.gc_clock.seconds,
                           "collections": sess.gc_clock.collections}
    result = {"correct": correct, "attempted": attempted, "failed": 0,
              "device": device}
    if trace:
        ctx = RunView(cell, spans, (w0, w1), prof.reduced, devices, batches)
        result["metrics"] = harness.read_per_layer(cell, ctx)
        result["device"].update(busy_s=prof.reduced["busy_s"],
                                window_s=prof.reduced["window_s"])
        result["breakdown"] = prof.reduced["breakdown"]
    else:
        e2e = end_to_end(batches, w0, w1, setup_s)
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                             for m in cell.end_to_end}
    return result, checks, {"timing": timing, "sample": reqs, "session": sess}


@dataclasses.dataclass
class RunView:
    """What a per-layer metric reader sees of a serving run."""
    cell: harness.Cell
    spans: harness.Spans
    window: tuple
    trace: dict | None
    devices: list
    batches: list
    kind: str = "serve"

    @property
    def peaks(self):
        return harness.peaks(self.devices[0].device_kind)
