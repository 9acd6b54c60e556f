"""CPU stage: milliseconds of decode plus augment per image, from the
``stage_decode`` and ``stage_augment`` spans that the process pool's
workers recorded and that ended in the window."""
from bench.metrics._spans import ended_in


def read(run):
    decode, augment = ended_in(run, "stage_decode"), ended_in(run, "stage_augment")
    if not decode:
        return None
    busy = sum(b - a for a, b in decode) + sum(b - a for a, b in augment)
    return 1e3 * busy / len(decode)
