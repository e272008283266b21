"""Host time per decode tick in the batch cells, moving tok_s: over the
decode ticks of the untraced part of the window, the mean of the
engine's `serve.decode.tick` span less its `serve.decode.wait` (the
wait for the device's step), in ms (lib/spans.py)."""
from chipbench.lib import spans


def read(run):
    recs = spans.window_records(run)
    return None if recs is None else spans.host_ms_per_tick(recs)
