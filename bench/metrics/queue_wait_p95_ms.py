"""95th percentile of the serving queue wait, in ms: submit to dequeue of
every request the traced window dequeued, from the ``wait_us`` argument of
the program's ``serve.batch`` spans (``benchkit/scopes.py``)."""
import numpy as np

from benchkit import scopes


def read(ctx):
    a = scopes.of_run(ctx)
    if a is None or not a["queue_waits_s"]:
        return None
    return 1e3 * float(np.percentile(a["queue_waits_s"], 95))
