"""Loader -> trainer (device ring): the share of the window in which the
step loop was blocked on the device ring, from the union of the trainer's
``loader_wait`` spans (one per ``next(ring)``) clipped to the window."""
from bench.trace import clip, total, union


def read(run):
    spans = run.spans.get("loader_wait")
    if not spans:
        return None
    w0, w1 = run.window
    return 100.0 * total(union(clip([(a, b) for a, b, _ in spans], w0, w1))) / (w1 - w0)
