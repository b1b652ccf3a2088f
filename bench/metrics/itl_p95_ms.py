"""95th percentile, in ms, of every gap between consecutive output tokens
of one request, over all requests, where both tokens reached the host in
the window.  Tokens delivered by one engine step arrive together."""
import numpy as np


def read(run):
    gaps = [b - a for r in run.requests
            for a, b in zip(r.stamps, r.stamps[1:])
            if run.in_window(a) and run.in_window(b)]
    return 1e3 * float(np.percentile(gaps, 95)) if gaps else None
