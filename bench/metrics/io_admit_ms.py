"""Storage / IO stage: the mean time, in milliseconds, that a sample waited
for an IO slot (``io_admit``: from its submission to the IO gate's permit),
over the samples admitted in the window. Large when the loader keeps more
samples outstanding than the IO stage has slots."""
from bench.metrics._spans import ended_in


def read(run):
    spans = ended_in(run, "io_admit")
    if not spans:
        return None
    return 1e3 * sum(b - a for a, b in spans) / len(spans)
