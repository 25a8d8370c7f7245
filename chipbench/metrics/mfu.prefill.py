"""Prefill's share of the chip's peak: the model FLOPs of every prefill in
the traced window over (device time of the prefill program x peak FLOP/s).
The device time is that of ``serve_loop``'s jitted ``_prefill``."""
import trace_reduce


def read(run):
    if run.kind != "serve" or run.trace is None:
        return None
    sec, calls = trace_reduce.module_seconds(run.trace, "jit__prefill")
    if not sec or round(calls) != len(run.batches):
        return None
    spec, cmod = run.cell.spec, run.cell.cmod
    flops = sum(b.size * cmod.prefill_flops(spec, b.prompt_len)
                for b in run.batches)
    return 100.0 * flops / (sec * run.peaks["bf16_flops_per_s"])
