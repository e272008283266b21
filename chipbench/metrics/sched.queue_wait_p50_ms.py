"""Scheduler queue wait: the median, over requests due in the window,
of the scheduler's admission stamp (`t_admit`) minus the due time."""
import numpy as np


def read(run):
    w = run.window
    waits = [(t.t_admit - t.due) * 1e3 for t in w.tracks
             if w.t0 <= t.due < w.t1 and t.t_admit]
    return float(np.median(waits)) if waits else None
