"""Jaxpr-level cost model.

XLA's ``compiled.cost_analysis()`` counts while/scan bodies ONCE (verified in
tests/test_costmodel.py), which silently undercounts scan-over-layers models
by ~n_layers.  This walker multiplies through ``lax.scan`` trip counts
exactly, giving the FLOP/byte numbers the roofline terms use.

Conventions:
  * FLOPs: 2*B*M*N*K per dot_general; elementwise ops counted at 1 flop per
    output element (they are VPU work, not MXU, but contribute to the
    compute term at the same peak for bf16 on v5e-class chips only via the
    vector unit — we keep them so fp32 SSD scans are visible).
  * Bytes: HBM-traffic proxy = operand + result bytes of data-moving ops
    (dot_general, gather/scatter, dynamic slices, conv, reduce, carried scan
    state) — elementwise ops are assumed fused (free).  This is a *model*,
    not a measurement; EXPERIMENTS.md reports it alongside XLA's
    fusion-aware-but-loop-blind "bytes accessed".
  * while loops count their body once (documented limitation; the code base
    avoids while for hot loops — triangular prefill uses a static-length
    pair scan precisely so it is countable).
  * Numbers are GLOBAL (pre-SPMD); callers divide by mesh size.  TP-
    replicated small projections are therefore slightly undercounted
    per-chip (documented in EXPERIMENTS.md).
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

_ELEMENTWISE = {
    "add", "sub", "mul", "div", "max", "min", "exp", "log", "tanh", "logistic",
    "rsqrt", "sqrt", "neg", "abs", "floor", "ceil", "round", "sign", "pow",
    "integer_pow", "select_n", "compare", "and", "or", "not", "xor",
    "convert_element_type", "erf", "cos", "sin",
}
_DATA_MOVERS = {
    "gather", "scatter", "scatter-add", "scatter_add", "dynamic_slice",
    "dynamic_update_slice", "concatenate", "pad", "reshape", "transpose",
    "broadcast_in_dim", "reduce_sum", "reduce_max", "reduce_min", "argmax",
    "argmin", "sort", "iota", "rev", "cumsum", "cumlogsumexp", "cummax",
    "take", "conv_general_dilated", "reduce_and", "reduce_or", "top_k",
    "select_and_scatter_add", "slice", "squeeze",
}
_CHEAP_MOVERS = {"reshape", "transpose", "broadcast_in_dim", "iota", "slice",
                 "squeeze"}  # usually layout no-ops / fused

_CALL_PRIMS = {"pjit", "closed_call", "core_call", "remat_call", "remat",
               "remat2", "custom_jvp_call", "custom_vjp_call",
               "custom_vjp_call_jaxpr", "checkpoint", "named_call",
               "shard_map", "smap"}


def _nbytes(aval) -> int:
    try:
        return int(np.prod(aval.shape, dtype=np.int64)) * aval.dtype.itemsize
    except Exception:
        return 0


def _size(aval) -> int:
    try:
        return int(np.prod(aval.shape, dtype=np.int64))
    except Exception:
        return 0


@dataclasses.dataclass
class CostStats:
    flops: float = 0.0            # MXU (dot) flops
    vector_flops: float = 0.0     # elementwise flops
    bytes: float = 0.0            # no-fusion HBM traffic (upper bound)
    bytes_fused: float = 0.0      # fusion-aware HBM traffic (roofline input)
    dot_bytes: float = 0.0
    while_bodies: int = 0         # loops counted once (should stay tiny)

    @property
    def total_flops(self) -> float:
        return self.flops + self.vector_flops

    def as_dict(self) -> dict:
        return {"flops": self.flops, "vector_flops": self.vector_flops,
                "bytes": self.bytes, "bytes_fused": self.bytes_fused,
                "dot_bytes": self.dot_bytes,
                "while_bodies": self.while_bodies}


def _dot_flops(eqn) -> float:
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    b = 1
    for d in lb:
        b *= lhs.shape[d]
    k = 1
    for d in lc:
        k *= lhs.shape[d]
    m = 1
    for i, d in enumerate(lhs.shape):
        if i not in lc and i not in lb:
            m *= d
    n = 1
    for i, d in enumerate(rhs.shape):
        if i not in rc and i not in rb:
            n *= d
    return 2.0 * b * m * n * k


def _walk(jaxpr, scale: float, st: CostStats):
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "scan":
            ln = eqn.params["length"]
            inner = eqn.params["jaxpr"].jaxpr
            # fusion-aware HBM model: one scan execution reads its stacked xs
            # once (e.g. per-layer weights), reads+writes the carry at the
            # boundary, and writes its stacked ys once.  Intermediates inside
            # a step are VMEM-resident (this is precisely the schedule the
            # Pallas kernels implement); gather/scatter/DUS inside still add
            # their slice traffic per trip below.
            nc = eqn.params.get("num_consts", 0)
            ncar = eqn.params.get("num_carry", 0)
            consts = eqn.invars[:nc]
            carry = eqn.invars[nc: nc + ncar]
            xs = eqn.invars[nc + ncar:]
            ys = eqn.outvars[ncar:]
            st.bytes_fused += scale * (
                sum(_nbytes(v.aval) for v in consts)
                + 2 * sum(_nbytes(v.aval) for v in carry)
                + sum(_nbytes(v.aval) for v in xs)
                + sum(_nbytes(v.aval) for v in ys))
            _walk(inner, scale * ln, st)
        elif name == "while":
            st.while_bodies += 1
            _walk(eqn.params["body_jaxpr"].jaxpr, scale, st)
        elif name == "cond":
            for br in eqn.params["branches"]:
                _walk(br.jaxpr, scale, st)
        elif name in _CALL_PRIMS:
            sub = (eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
                   or eqn.params.get("fun_jaxpr"))
            if sub is not None:
                _walk(sub.jaxpr if hasattr(sub, "jaxpr") else sub, scale, st)
        elif name == "dot_general":
            f = _dot_flops(eqn)
            st.flops += scale * f
            io = sum(_nbytes(v.aval) for v in eqn.invars) \
                + sum(_nbytes(v.aval) for v in eqn.outvars)
            st.bytes += scale * io
            st.dot_bytes += scale * io
        elif name in _ELEMENTWISE or name.startswith("reduce_precision"):
            st.vector_flops += scale * max(
                (_size(v.aval) for v in eqn.outvars), default=0)
        elif name in _DATA_MOVERS:
            if name in _CHEAP_MOVERS:
                continue
            if name == "dynamic_slice":
                # reads only the slice, not the whole operand
                io = sum(_nbytes(v.aval) for v in eqn.outvars)
            elif name == "dynamic_update_slice":
                # read+write of the updated region (in-place on TPU/XLA)
                io = 2 * _nbytes(eqn.invars[1].aval)
            elif name in ("gather", "take"):
                io = 2 * sum(_nbytes(v.aval) for v in eqn.outvars)
            elif name.startswith("scatter"):
                upd = eqn.invars[2].aval if len(eqn.invars) > 2 else eqn.invars[-1].aval
                io = 3 * _nbytes(upd)        # read dst, read upd, write dst
            else:
                io = sum(_nbytes(v.aval) for v in eqn.invars) \
                    + sum(_nbytes(v.aval) for v in eqn.outvars)
            st.bytes += scale * io
            if name in ("gather", "take", "dynamic_slice",
                        "dynamic_update_slice") or name.startswith("scatter"):
                st.bytes_fused += scale * io
            if name in ("reduce_sum", "reduce_max", "reduce_min", "cumsum"):
                st.vector_flops += scale * max(
                    (_size(v.aval) for v in eqn.invars), default=0)
        else:
            # unknown primitive: count result bytes conservatively
            st.bytes += scale * sum(_nbytes(v.aval) for v in eqn.outvars)


def cost_of(fn, *args) -> CostStats:
    """Trace fn abstractly and return scan-exact global cost stats."""
    closed = jax.make_jaxpr(fn)(*args)
    st = CostStats()
    _walk(closed.jaxpr, 1.0, st)
    # program inputs/outputs touch HBM once
    io = sum(_nbytes(v.aval) for v in closed.jaxpr.invars) \
        + sum(_nbytes(v.aval) for v in closed.jaxpr.outvars)
    st.bytes += io
    st.bytes_fused += io
    return st

