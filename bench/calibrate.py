"""Readings that the limits of ``bench/limits/<cell>.json`` are set from.

    python3 bench/calibrate.py --workload rn18.s3 --seeds 11,12,13 --out chiprun_out/cal.jsonl

For each seed, in one process: a run of the cell with a short window, whose
check numbers are the program's readings (the lower end of each limit);
then, on the same reference batches, the readings of

* ``control`` -- the reference computed in bfloat16, put in the program's
  place (the step a later change would be tempted to take);
* ``control_step`` -- the same with the ingest kept in float32: only the
  step runs in bfloat16, so only the step's numbers can catch it;
* ``half`` -- the reference stepping on half of each batch;
* ``unchanged`` -- a step that leaves the state as it was;
* ``program_highest`` -- the program's own step at ``highest`` precision on
  the reference's batches: a witness of where the program's gaps come from.

Each reading is also compared, under ``vs_config``, with the reference run
at the matmul precision the configuration states (``matmul_precision``), as
the program runs: against it the program's own precision drops out.

Each row also names the worst leaf of the gradient and update gaps.

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

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import check, harness, spec
    from bench.reference import model as ref_model

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

    def leaf_names(cfg):
        shapes = jax.eval_shape(lambda k: ref_model.init_params(
            k, tuple(cfg["resnet_blocks"]), int(cfg["resnet_width"]), int(cfg["num_classes"])),
            jax.random.PRNGKey(0))
        return [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(shapes)[0]]

    def program_highest(cell, seed, ref):
        from repro.train.steps import init_resnet_train_state, make_resnet_train_step

        mcfg, tcfg = harness.program_configs(cell.config)
        with jax.default_matmul_precision("highest"):
            key = jax.random.PRNGKey(spec.derive(seed, "weights", 31))
            state = jax.jit(lambda k: init_resnet_train_state(mcfg, tcfg, k))(key)
            p0 = harness.host_leaves(state["params"])
            step = jax.jit(make_resnet_train_step(mcfg, tcfg))
            losses, mu1 = [], None
            for i, (images, (_, labels)) in enumerate(zip(ref["normalized"], ref["batches"])):
                state, m = step(state, {"image": jnp.asarray(images),
                                        "label": jnp.asarray(labels)})
                losses.append(float(m["loss"]))
                if i == 0:
                    mu1 = harness.host_leaves(state["opt"]["mu"])
            return {"losses": losses, "mu1": mu1, "p0": p0,
                    "p3": harness.host_leaves(state["params"])}

    def readings(cell, seed, ref, prog, numbers):
        beta1 = float(cell.config["train"]["beta1"])
        batch = int(cell.config["batch_per_chip"])
        names = leaf_names(cell.config)
        details = {}

        def detail(key, like):
            grad, update = check.gap_leaves(like, ref, beta1)
            details[key] = {"grad_worst": names[int(np.nanargmax(grad))],
                            "update_worst": names[int(np.nanargmax(update))]}

        def in_place(out, batches_from=None):
            """A reference run put in the program's place; its batches are
            its own normalisation, or ``batches_from``'s."""
            normalized = (batches_from or out)["normalized"]
            return {"batches": [(n, b[1]) for n, b in zip(normalized, ref["batches"])],
                    "ingest": normalized, "losses": out["losses"],
                    "mu1": [g * (1 - beta1) for g in out["g1"]], "p0": out["p0"],
                    "p3": out["p3"]}

        planted = {"program": prog}
        for key, ingest in (("control", jnp.bfloat16), ("control_step", jnp.float32)):
            planted[key] = in_place(harness.reference_steps(
                cell, ref["batches"], seed, dtype=jnp.bfloat16, ingest_dtype=ingest))
        planted["half"] = in_place(
            harness.reference_steps(cell, ref["batches"], seed, rows=batch // 2), ref)
        faithful = {"batches": planted["half"]["batches"], "ingest": ref["normalized"]}
        planted["program_highest"] = dict(faithful, **program_highest(cell, seed, ref))
        for key in ("program", "control", "control_step", "program_highest"):
            detail(key, planted[key])
        ref_cfg = dict(harness.reference_steps(cell, ref["batches"], seed,
                                               precision=cell.config["matmul_precision"]),
                       batches=ref["batches"])
        vs_config = {k: check.compare(v, ref_cfg, beta1) for k, v in planted.items()}
        rows = {k: check.compare(v, ref, beta1) for k, v in planted.items()}
        rows["unchanged"] = check.compare(dict(
            faithful, losses=[ref["losses"][0]] * len(ref["losses"]),
            mu1=[np.zeros_like(g) for g in ref["g1"]], p0=ref["p0"], p3=ref["p0"]), ref, beta1)
        line = {"workload": cell.name, "seed": seed, **rows, "leaves": details,
                "vs_config": vs_config}
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
