"""Storage / IO stage: the mean number of IO gate permits held over the
window. A permit is held through a sample's GET (``stage_fetch``) and then
until the fetch->decode queue takes the sample (``io_handoff``); both are
clipped to the window and summed. Hedged duplicates hold no permit and are
left out. Near the IO width (``io_workers``) the IO stage sets the pace."""
from bench.trace import clip, total


def _held(run, name):
    w0, w1 = run.window
    return total(clip([(a, b) for a, b, args in run.spans.get(name, [])
                       if not args.get("hedge")], w0, w1))


def read(run):
    if not run.spans.get("io_handoff"):
        return None
    return (_held(run, "stage_fetch") + _held(run, "io_handoff")) / run.seconds
