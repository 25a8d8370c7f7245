"""p95 over the traced window's requests of the time per output token,
(done - first token) / (new tokens - 1), on the host clock: the same
quantity as the end-to-end ``tpot_p95_ms``, read per layer in cells whose
closed batches make that tail too unsteady to bound."""
import harness


def read(run):
    if run.kind != "serve" or not run.batches:
        return None
    return harness.load_module(harness.HERE / "drive_serve.py").tpot_p95_ms(
        run.batches)
