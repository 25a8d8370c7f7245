"""Training's share of the chip's peak: forward and backward model FLOPs
per token (recompute not counted) x tokens per second of the traced window,
over peak FLOP/s."""


def read(run):
    if run.kind != "train" or run.steps == 0:
        return None
    mix = run.cell.traffic
    per_token = run.cell.cmod.train_flops_per_token(run.cell.spec, mix["seq"])
    return 100.0 * per_token * run.tokens_per_s / run.peaks["bf16_flops_per_s"]
