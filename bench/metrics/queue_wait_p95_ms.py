"""Admission layer: 95th percentile, in ms, of the wait from when a request
was due to the engine step whose admission gave it a slot (the engine's
``Request.start``), over requests due and admitted in the window."""
import numpy as np


def read(run):
    xs = [r.request.start - r.due for r in run.requests
          if run.in_window(r.due) and r.request is not None
          and r.request.start >= 0 and run.in_window(r.request.start)]
    return 1e3 * float(np.percentile(xs, 95)) if xs else None
