"""Scheduler: 95th percentile, over the requests due in the window, of the
wait from due time to admission (the engine's ``Request.admit_t``, on the
same host clock).  A request still queued at the window's close counts
its wait so far."""
import numpy as np


def read(run):
    ws, we = run["window"]
    waits = [min(r["admit_t"] or we, we) - r["due"]
             for r in run["requests"] if r["in_window"]]
    if not waits:
        return None
    return float(np.percentile(waits, 95))
