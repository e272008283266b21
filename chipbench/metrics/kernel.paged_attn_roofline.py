"""Paged decode attention (kernels/paged_attention.py) against its
roofline: for each traced decode tick and layer, the least time that
reading q and the K/V of every live context token (and the flops over
them) allows, summed, over the kernel's summed device time, in %."""
from chipbench.lib.roofline import min_time, paged_attn_cost


def read(run):
    if run.trace is None:
        return None
    from chipbench.metrics._common import calls, in_trace
    cs = calls(run, "paged_attention")
    ticks = in_trace(run.loop.ticks, run)
    if not cs or not ticks:
        return None
    dm = run.dm
    kv = 2.0 if run.config["serve"]["kv_bits"] == 0 else None
    if kv is None:
        return None
    need = sum(dm["layers"] * min_time(*paged_attn_cost(
        live, ctx, dm["heads"], dm["kv_heads"], dm["hd"], kv), run.peaks)
        for _, _, live, ctx in ticks)
    return 100.0 * need / (sum(c[1] for c in cs) / 1e9)
