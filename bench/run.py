"""Run one benchmark cell and print its result as the last line.

    python3 bench/run.py --workload rn18.s3 --seed 7 --seconds 51 --trace 0

Nothing but the standard library is imported at the top: the CPU stage's
spawned workers import this file again, and they must never touch jax.
"""
import time

T_START = time.monotonic()

import os  # noqa: E402
import sys  # noqa: E402

if __name__ == "__main__":
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [root, os.path.join(root, "src")]
    from bench import harness

    sys.exit(harness.main(sys.argv[1:], T_START))
