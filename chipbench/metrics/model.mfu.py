"""Whole-step model FLOP utilization: the useful model FLOPs of the
prompt and output tokens processed in the traced span (roofline.py:
model_flops) over the span's length and the bf16 peak of the cell's
chips, in %."""
from chipbench.lib.roofline import model_flops


def read(run):
    if run.trace is None:
        return None
    from chipbench.metrics._common import in_trace
    adm = in_trace(run.loop.admissions, run)
    ticks = in_trace(run.loop.ticks, run)
    if not adm and not ticks:
        return None
    f = model_flops(run.dm, adm, ticks)
    peak = run.peaks["bf16_flops_per_s"] * run.chips
    return 100.0 * f / run.trace["window_s"] / peak
