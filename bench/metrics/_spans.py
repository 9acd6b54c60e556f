"""Helpers the span readers share: spans that end inside the window."""


def ended_in(run, name):
    w0, w1 = run.window
    return [(a, b) for a, b, _ in run.spans.get(name, []) if w0 < b <= w1]
