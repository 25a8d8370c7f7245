"""Host milliseconds per ``Governor.plan`` call in a serving window, from
the benchmark's proxy around the governor (``harness.PlanTimer``)."""
import statistics


def read(run):
    if run.kind != "serve":
        return None
    d = run.spans.durations("cb.plan", *run.window)
    return 1e3 * statistics.fmean(d) if d else None
