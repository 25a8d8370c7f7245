"""Training cells: one call of ``repro.runtime.train_loop.train``, fed and
stopped by the benchmark.

``train()`` builds its step, state and governor hook once and loops.  The
benchmark takes the place of two names inside that loop and of nothing
else: ``make_batch`` becomes :class:`Feed`, which hands out the seeded
traffic and stamps every step boundary (the end of a step, its host work
included), and ``retry_step`` becomes :class:`Probe`, which delegates and,
on the first steps only, keeps what ``correct`` needs.  The first
``checked_steps`` steps and one more are set-up; the window runs from the
end of that step for ``--seconds``, and the feed ends the loop at the next
boundary after it.

``correct`` follows the first three steps with the plain reference, from
the same seed and rows: each step's loss, each leaf's norm of the first
gradient as the optimizer got it (its first moment after one step over
``1 - b1``), and each leaf's norm of the parameters' change over the three
steps, read from the state step four receives.
"""
from __future__ import annotations

import dataclasses
import gc

import jax
import jax.numpy as jnp
import numpy as np
from repro.configs.shapes import ShapeSpec
from repro.parallel.sharding import make_env
from repro.runtime import train_loop

import generator
import harness
import refops
from harness import now


class WindowClosed(Exception):
    """Raised by the feed at the first step boundary after the window."""


def _norms(xs):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in xs]


def leaf_norms(tree) -> dict[str, float]:
    """{leaf path: float32 norm} of a parameter-shaped tree."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    norms = jax.jit(_norms)([x for _, x in flat])
    return {jax.tree_util.keystr(p): float(n) for (p, _), n in zip(flat, norms)}


def diff_norms(a, b) -> dict[str, float]:
    return leaf_norms(jax.tree.map(
        lambda x, y: x.astype("float32") - y.astype("float32"), a, b))


class Probe:
    """Stands in for ``retry_step``: delegates every step; on the first
    ones keeps the losses, a copy of the initial parameters, the first
    gradient's leaf norms and the change's leaf norms."""

    def __init__(self, retry_step, checked: int, b1: float, spans):
        self.retry_step, self.checked, self.b1 = retry_step, checked, b1
        self.spans = spans
        self.calls = 0
        self.losses, self.p0 = [], None
        self.grad_norms = self.change_norms = None

    def __call__(self, fn, params, opt_state, batch, **kw):
        k = self.calls
        self.calls += 1
        if k == 0:
            self.p0 = jax.tree.map(jnp.copy, params)
        if k == self.checked:
            self.change_norms = diff_norms(params, self.p0)
            self.p0 = None
        with self.spans("cb.step"):
            out = self.retry_step(fn, params, opt_state, batch, **kw)
        if k < self.checked:
            self.losses.append(out[0])
        if k == 0:
            self.grad_norms = {p: v / (1 - self.b1)
                               for p, v in leaf_norms(out[2]["m"]).items()}
        return out


class Feed:
    """Stands in for ``make_batch``: the seeded batch of each step, a host
    span around it, and the step boundaries of the window."""

    def __init__(self, traffic, first_timed: int, seconds: float, spans,
                 prof: harness.Profiler):
        self.traffic, self.first_timed, self.seconds = traffic, first_timed, seconds
        self.spans, self.prof = spans, prof
        self.asked: list[float] = []        # host time of every call
        self.boundaries: list[float] = []   # those of the window
        self.gc_clock = harness.GcClock()
        self._window = None

    def __call__(self, cfg, shape, step=0, seed=0):
        t = now()
        self.asked.append(t)
        if step == self.first_timed:
            self.prof.start()
            self._window = self.spans("cb.window").__enter__()
            self.gc_clock.__enter__()
        if step >= self.first_timed:
            self.boundaries.append(t)
            if len(self.boundaries) > 1 and t - self.boundaries[0] >= self.seconds:
                self.gc_clock.__exit__(None, None, None)
                self._window.__exit__(None, None, None)
                self.prof.stop()
                raise WindowClosed
        with self.spans("cb.make_batch"):
            return {"tokens": jnp.asarray(self.traffic.batch_tokens(step))}


def reference_steps(cmod, spec, opt, key, traffic, n, q=None, rows=None):
    """The plain reference's first ``n`` AdamW steps from the seeded
    weights: losses, first-gradient leaf norms, change leaf norms and the
    first gradient's leaf norms before clipping.  ``rows`` keeps only that
    many rows of each batch (a planted fault)."""
    quant = refops.QUANTIZERS[q]
    init = jax.jit(lambda k: cmod.init_params(k, spec))
    p0 = init(key)
    dtypes = jax.tree.map(lambda x: x.dtype, p0)

    @jax.jit
    def step(p, m, v, t, tokens):
        loss, g = jax.value_and_grad(
            lambda pf: cmod.loss(pf, tokens, spec, quant))(
            jax.tree.map(lambda x: x.astype(jnp.float32), p))
        gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        clip = jnp.minimum(1.0, opt["grad_clip"] / (gnorm + 1e-9))
        bc1, bc2 = 1 - opt["b1"] ** t, 1 - opt["b2"] ** t

        def upd(x, gi, mi, vi, dt):
            gi = gi * clip
            mi = opt["b1"] * mi + (1 - opt["b1"]) * gi
            vi = opt["b2"] * vi + (1 - opt["b2"]) * gi * gi
            xf = x.astype(jnp.float32)
            xf = xf - opt["lr"] * ((mi / bc1) / (jnp.sqrt(vi / bc2) + opt["eps"])
                                   + opt["weight_decay"] * xf)
            return xf.astype(dt), mi, vi

        out = jax.tree.map(upd, p, g, m, v, dtypes)
        is_t = lambda x: isinstance(x, tuple)
        pick = lambda i: jax.tree.map(lambda o: o[i], out, is_leaf=is_t)
        return loss, pick(0), pick(1), pick(2), g, clip

    zeros = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), p0)
    p, m, v = p0, zeros, zeros
    losses = []
    for k in range(n):
        tokens = jnp.asarray(traffic.batch_tokens(k)[:rows])
        loss, p, m, v, g, clip = step(p, m, v, jnp.float32(k + 1), tokens)
        losses.append(float(loss))
        if k == 0:
            raw = leaf_norms(g)
            grads = {name: val * float(clip) for name, val in raw.items()}
    return {"loss": losses, "grad": grads, "change": diff_norms(p, p0),
            "raw_grad": raw}


def worst_leaf_gap(prog: dict, ref: dict, keep=None) -> float:
    """max over leaves of |program norm - reference norm| over the larger
    of that leaf's reference norm and the median leaf's."""
    names = [n for n in ref if keep is None or n in keep]
    med = float(np.median([ref[n] for n in names]))
    return max(abs(prog[n] - ref[n]) / max(ref[n], med) for n in names)


def moved_leaves(raw_grad: dict) -> set:
    """Leaves whose reference gradient is above a thousandth of the median
    leaf's; the others move by round-off alone."""
    med = float(np.median(list(raw_grad.values())))
    return {n for n, v in raw_grad.items() if v >= 1e-3 * med}


def compare(prog: dict, ref: dict) -> dict:
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["loss"], ref["loss"]))
    return {"loss_gap": loss,
            "grad_norm_gap": worst_leaf_gap(prog["grad"], ref["grad"]),
            "change_norm_gap": worst_leaf_gap(prog["change"], ref["change"],
                                              moved_leaves(ref["raw_grad"]))}


def run(cell, seed, seconds, trace, t0, devices):
    mix, opt = cell.traffic, cell.traffic["optimizer"]
    spans = harness.Spans()
    cfg = harness.program_config(cell.spec)
    env = make_env(cfg, None)
    shape = ShapeSpec("chipbench", mix["seq"], mix["batch"], "train")
    traffic = generator.make(mix, cell.spec["vocab"], seed)
    t = now()
    gov, gdev, regions = harness.build_train_governor(mix["governor"])
    parts = {"start_s": t - t0, "governor_s": now() - t}
    planner = harness.PlanTimer(gov, spans)
    checked = mix["checked_steps"]
    prof = harness.Profiler(trace)
    feed = Feed(traffic, checked + 1,
                min(seconds, mix["trace_seconds"]) if trace else seconds,
                spans, prof)
    probe = Probe(train_loop.retry_step, checked, opt["b1"], spans)
    tc = train_loop.TrainConfig(steps=10 ** 9, lr=opt["lr"],
                                seed=harness.init_seed(seed))
    saved = train_loop.make_batch, train_loop.retry_step
    train_loop.make_batch, train_loop.retry_step = feed, probe
    t_train = now()
    try:
        with spans("cb.train"):
            train_loop.train(cfg, shape, env, tc, governor=planner,
                             device=gdev, regions=regions)
    except WindowClosed:
        pass
    finally:
        train_loop.make_batch, train_loop.retry_step = saved
    device = harness.device_info(devices)
    b = feed.boundaries
    setup_s = b[0] - t0
    step_s = np.diff(b)
    slowest = np.argsort(step_s)[::-1][:3]
    # train() up to its first batch is the program's own set-up (eager
    # weights, optimizer state); then the steps before the window, the
    # first of which compiles or loads the step from the cache.
    parts["init_s"] = feed.asked[0] - t_train
    parts["setup_steps_s"] = np.diff(feed.asked[:checked + 2]).tolist()
    parts["step_s"] = {"min": float(step_s.min()), "median": float(np.median(step_s)),
                       "max": float(step_s.max())}
    parts["window_gc"] = {"seconds": feed.gc_clock.seconds,
                          "collections": feed.gc_clock.collections}
    # Where the slowest steps' time went on the host: the step's dispatch,
    # the governor's plans and the feed; the rest is the wait on the device
    # (``float(loss)``) and the loop's own lines.
    parts["slowest_steps"] = [
        {"step": int(j), "s": float(step_s[j]),
         **{name: sum(spans.durations(f"cb.{name}", b[j], b[j + 1]))
            for name in ("step", "plan", "make_batch")}}
        for j in slowest]
    steps = len(b) - 1
    window = (b[0], b[-1])
    prog = {"loss": [float(x) for x in probe.losses],
            "grad": probe.grad_norms, "change": probe.change_norms}
    del probe, feed
    gc.collect()
    jax.clear_caches()

    t = now()
    ref = reference_steps(cell.cmod, cell.spec, opt,
                          jax.random.PRNGKey(harness.init_seed(seed)), traffic,
                          checked)
    parts.update(setup_s=setup_s, reference_s=now() - t)
    gaps = compare(prog, ref)
    checks = {k: {"value": gaps[k], "limit": lim} for k, lim in cell.limits.items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values()) and all(
        np.isfinite(prog["loss"]))
    result = {"correct": bool(correct), "attempted": steps, "failed": 0,
              "device": device}
    view = RunView(cell, spans, window, prof.reduced, devices, steps,
                   mix["batch"] * mix["seq"])
    if trace:
        result["metrics"] = harness.read_per_layer(cell, view)
        result["device"].update(busy_s=prof.reduced["busy_s"],
                                window_s=prof.reduced["window_s"])
        result["breakdown"] = prof.reduced["breakdown"]
    else:
        e2e = {"train_tokens_per_s": view.tokens_per_s, "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                             for m in cell.end_to_end}
    return result, checks, {"timing": parts, "program": prog, "reference": ref}


@dataclasses.dataclass
class RunView:
    """What a per-layer metric reader sees of a training run."""
    cell: harness.Cell
    spans: harness.Spans
    window: tuple
    trace: dict | None
    devices: list
    steps: int
    tokens_per_step: int
    kind: str = "train"

    @property
    def peaks(self):
        return harness.peaks(self.devices[0].device_kind)

    @property
    def tokens_per_s(self):
        return self.steps * self.tokens_per_step / (self.window[1] - self.window[0])
