"""Readings that the limits of ``bench/limits/<cell>.json`` are set from.

    python3 bench/calibrate.py --workload rn18.s3 --seeds 11,12,13 --out chiprun_out/cal.jsonl

For each seed, in one process: a run of the cell with a short window, whose
check numbers are the program's readings (the lower end of each limit);
then, on the same reference batches, the readings of the controls and
planted faults that the cell's family makes (``readings`` in
``bench/families/<family>.py``; for ResNet the bfloat16 control, the same
with the ingest kept in float32, half of each batch, a step that leaves the
state unchanged, and the program's own step at ``highest`` precision).

The benchmark's own runs never do this. One JSON line per seed goes to
``--out``.
"""
import os
import sys
import time

T_START = time.monotonic()

if __name__ == "__main__":
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [root, os.path.join(root, "src")]
    import argparse
    import json

    from bench import harness, spec

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--pool-dir", default=None)
    a = ap.parse_args()
    cell = spec.resolve(a.workload, spec.load_benchmark())
    if a.rehearse:
        cell = harness.rehearsal(cell)
    devices = harness.setup_jax(cell, a.rehearse)
    os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)

    def readings(cell, seed, ref, prog, numbers):
        t1 = time.monotonic()
        line = {"workload": cell.name, "seed": seed,
                **cell.family.readings(cell.config, seed, ref, prog)}
        harness.log(f"seed {seed}: run and check {t1 - t0:.1f} s, "
                    f"readings {time.monotonic() - t1:.1f} s")
        print(json.dumps(line), flush=True)
        with open(a.out, "a") as f:
            f.write(json.dumps(line) + "\n")

    for seed in [int(s) for s in a.seeds.split(",")]:
        argv = ["--workload", a.workload, "--seed", str(seed), "--seconds", str(a.seconds)]
        if a.pool_dir:
            argv += ["--pool-dir", a.pool_dir]
        args = harness.parse_args(argv)
        t0 = time.monotonic()
        res = harness.run(cell, args, devices, t0, on_check=readings)
        print(json.dumps({"seed": seed, "result": res}), flush=True)
