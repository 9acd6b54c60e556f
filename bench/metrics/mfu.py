"""Train step: model FLOP utilization while the step runs. The training
FLOPs of one step that the configuration's shapes require (bench/flops.py:
6 per multiply-add of the forward pass, times the images of a step) over
the step program's device time per step (as ``step_device_ms`` reads it
from the trace), as a share of the chips' bf16 peak (bench/peaks.json).
It is taken from device time, so how fast the host feeds the chip does
not move it; the host's share shows in ``train_images_per_s``."""
from bench.metrics import step_device_ms


def read(run):
    ms = step_device_ms.read(run)
    if not ms:
        return None
    achieved = run.flops_per_image() * run.images_per_step / (ms / 1e3)
    return 100.0 * achieved / (run.chips * run.peaks["bf16_flops_per_s"])
