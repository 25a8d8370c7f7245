"""One ``pallas_call`` for every backend.

Mosaic compiles a Pallas kernel only for a TPU; the CPU backend can run it
only in interpret mode.  :func:`pallas_call` decides at lowering time, from
the platform the program is lowered for — which is where its arrays live —
so a kernel is compiled on a TPU and interpreted on the CPU (the tests),
with no option a caller could set to interpret on a chip.
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl


def pallas_call(body, **kwargs):
    """``pl.pallas_call(body, **kwargs)``: compiled when lowered for a TPU,
    interpreted on every other platform."""
    compiled = pl.pallas_call(body, interpret=False, **kwargs)
    interpreted = pl.pallas_call(body, interpret=True, **kwargs)

    def call(*args):
        return jax.lax.platform_dependent(*args, tpu=compiled,
                                          default=interpreted)
    return call
