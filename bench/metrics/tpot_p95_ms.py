"""95th percentile of the time per output token, in ms (host clock).

Each delivery of ``k`` tokens to a request after its first delivery
gives each of those tokens the gap since that request's previous
delivery, over ``k``; the percentile runs over every such token
delivered in the window, so a stalled step moves it."""
import numpy as np


def read(run):
    got = [d for d in run.window.deliveries if d.gap is not None]
    if not got:
        return None
    per_token = np.repeat([d.gap for d in got], [d.k for d in got])
    return 1000.0 * float(np.percentile(per_token, 95))
