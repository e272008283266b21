"""Gap between decode steps in the chat cell, moving itl_mean_ms: over
consecutive decode ticks of the untraced part of the window, the p95 of
the start of the next `serve.decode.dispatch` less the end of this
`serve.decode.wait`, in ms: how long the device had no decode step
queued, admissions and harness work included (lib/spans.py)."""
import numpy as np

from chipbench.lib import spans


def read(run):
    recs = spans.window_records(run)
    gaps = spans.tick_gaps_ms(recs) if recs is not None else []
    return float(np.percentile(gaps, 95)) if gaps else None
