"""95th percentile of time to first token, in ms, over the requests due in
the window whose first token reached the host inside it.  Timed from when
each request was due (open loop), to the end of the engine step that
delivered the token."""
import numpy as np


def read(run):
    xs = [r.stamps[0] - r.due for r in run.requests
          if run.in_window(r.due) and r.stamps and run.in_window(r.stamps[0])]
    return 1e3 * float(np.percentile(xs, 95)) if xs else None
