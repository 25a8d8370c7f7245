"""Decode step's share of the chip's peak by its bounding roof: over every
decode step in the traced window, the larger of (model FLOPs / peak FLOP/s)
and (bytes the step must move / peak bytes/s), summed, over the device time
of ``serve_loop``'s jitted ``_decode_step``."""
import trace_reduce


def read(run):
    if run.kind != "serve" or run.trace is None:
        return None
    sec, calls = trace_reduce.module_seconds(run.trace, "jit__decode_step")
    steps = sum(b.new_tokens - 1 for b in run.batches)
    if not sec or round(calls) != steps:
        return None
    spec, cmod, pk = run.cell.spec, run.cell.cmod, run.peaks
    bound = 0.0
    for b in run.batches:
        for i in range(b.new_tokens - 1):
            pos = b.prompt_len + i
            bound += max(b.size * cmod.decode_flops(spec, pos) / pk["bf16_flops_per_s"],
                         cmod.decode_bytes(spec, b.size, pos) / pk["hbm_bytes_per_s"])
    return 100.0 * bound / sec
