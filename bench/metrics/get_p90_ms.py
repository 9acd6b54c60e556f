"""Storage / IO stage: 90th percentile of the ``stage_fetch`` spans that
ended in the window, in milliseconds (connection-pool wait included)."""
import numpy as np

from bench.metrics._spans import ended_in


def read(run):
    spans = ended_in(run, "stage_fetch")
    if len(spans) < 10:
        return None
    return float(np.percentile([1e3 * (b - a) for a, b in spans], 90))
