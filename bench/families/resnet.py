"""The ResNet family: image classification on RIMG images (the paper's job).

The program's side: ``ImageDataset`` over the benchmark's store, the
``make_ingest_fn`` epilogue on the device, and the jitted
``make_resnet_train_step`` on a state from ``init_resnet_train_state``. The
references: ``bench/reference/loader.py`` (the batches) and
``bench/reference/model.py`` (a plain float32 ResNet and its AdamW step).
The interface is in ``bench/families/__init__.py``.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench import check, flops, spec, storage
from bench.reference import loader as ref_loader
from bench.reference import model as ref_model

PREFIX = storage.PREFIX


def load_pool(objects: Dict, pool_dir: str) -> storage.Pool:
    return storage.load_pool(objects, pool_dir)


def rehearse(config: Dict, objects: Dict):
    config = dict(config, resnet_blocks=[1, 1], resnet_width=8, image_size=32,
                  batch_per_chip=8)
    return config, dict(objects, pool=16, height=48, width=64, coarse=4)


def samples_per_step(config: Dict) -> int:
    return int(config["batch_per_chip"])


def dataset(config: Dict, traffic: Dict, store, seed: int, tracer, fault: str):
    from repro.data.dataset import ImageDataset

    from bench import faults

    cls = faults.AlteredDataset if fault == "alter" else ImageDataset
    return cls(store, int(traffic["keyspace"]), prefix=PREFIX,
               out_size=int(config["image_size"]), seed=spec.derive(seed, "aug"),
               tracer=tracer, sim_decode_s_per_mb=0.0, epilogue=traffic["loader"]["epilogue"])


def program_configs(config: Dict):
    """The program's ModelConfig and TrainConfig for a configuration file."""
    from repro.config import ModelConfig, TrainConfig

    mcfg = ModelConfig(name=config["name"], family="resnet",
                       resnet_blocks=tuple(config["resnet_blocks"]),
                       resnet_width=int(config["resnet_width"]),
                       num_classes=int(config["num_classes"]),
                       image_size=int(config["image_size"]))
    return mcfg, TrainConfig(**config["train"])


def init_state(config: Dict, seed: int):
    import jax

    from repro.train.steps import init_resnet_train_state

    mcfg, tcfg = program_configs(config)
    key = jax.random.PRNGKey(spec.derive(seed, "weights", 31))
    return jax.jit(lambda k: init_resnet_train_state(mcfg, tcfg, k))(key)


def make_step(config: Dict):
    from repro.train.steps import make_resnet_train_step

    return make_resnet_train_step(*program_configs(config))


def trainer_options(config: Dict, traffic: Dict) -> Dict:
    """The ingest kernel, where the loader leaves the normalisation to the
    device."""
    from repro.kernels.ingest_norm.ops import make_ingest_fn

    return {"ingest_fn": make_ingest_fn()} if traffic["loader"]["epilogue"] == "device" else {}


def warm_batch(config: Dict, options: Dict):
    """Zeros through the ingest kernel (which compiles it), or normalised
    zeros where the host normalises."""
    import jax

    side, batch = int(config["image_size"]), samples_per_step(config)
    label = jax.device_put(np.zeros((batch,), np.int32))
    ingest_fn = options.get("ingest_fn")
    if ingest_fn is not None:
        raw = jax.device_put(np.zeros((batch, side, side, 3), np.uint8))
        images = ingest_fn({"image": raw, "label": label})["image"]
    else:
        images = jax.device_put(np.zeros((batch, 3, side, side), np.float32))
    return {"image": images, "label": label}


def keep(batch):
    import jax

    img, lab = jax.device_get((batch["image"], batch["label"]))
    return np.asarray(img), np.asarray(lab)


def reference(config: Dict, traffic: Dict, pool, seed: int) -> Dict:
    """The reference loader's first batches and the reference step on them,
    in float32 at ``highest`` precision."""
    store = storage.PoolStore(pool, int(traffic["keyspace"]), spec.derive(seed, "store"))
    batches = ref_loader.batches(
        store, keyspace=int(traffic["keyspace"]), batch=samples_per_step(config),
        count=check.CHECKED_STEPS, sampler_seed=spec.derive(seed, "sampler"),
        aug_seed=spec.derive(seed, "aug"), out=int(config["image_size"]), prefix=PREFIX)
    return dict(reference_steps(config, batches, seed), batches=batches)


def reference_steps(config: Dict, ref_batches, seed: int, dtype=None, rows=None,
                    ingest_dtype=None, precision: str = "highest") -> Dict:
    """Three reference steps from the seed's weights on ``ref_batches``.
    ``dtype`` is the step's type (default float32) and ``precision`` its
    matmul precision, ``ingest_dtype`` the normalisation's type (default
    ``dtype``); with ``rows`` the step sees only the first rows of each
    batch. They make the controls and the half-batch fault."""
    import jax
    import jax.numpy as jnp

    dtype = dtype or jnp.float32
    ingest_dtype = ingest_dtype or dtype
    blocks = tuple(config["resnet_blocks"])
    with jax.default_matmul_precision(precision):
        key = jax.random.PRNGKey(spec.derive(seed, "weights", 31))
        init = jax.jit(ref_model.init_params, static_argnums=(1, 2, 3))
        params = init(key, blocks, int(config["resnet_width"]), int(config["num_classes"]))
        p0 = check.host_leaves(params)
        mu = jax.tree.map(jnp.zeros_like, params)
        nu = jax.tree.map(jnp.zeros_like, params)
        step = ref_model.make_step(blocks, config["train"], dtype, rows)
        norm = jax.jit(ref_model.normalize, static_argnums=(1,))
        losses, normalized, g1 = [], [], None
        for i, (u8, labels) in enumerate(ref_batches):
            images = norm(jnp.asarray(u8), ingest_dtype)
            normalized.append(np.asarray(images.astype(jnp.float32)))
            params, mu, nu, loss, g = step(params, mu, nu, jnp.int32(i), images,
                                           jnp.asarray(labels))
            losses.append(float(loss))
            if i == 0:
                g1 = check.host_leaves(g)
        return {"losses": losses, "normalized": normalized, "g1": g1, "p0": p0,
                "p3": check.host_leaves(params)}


def recover_u8(images_nchw: np.ndarray) -> np.ndarray:
    """Normalised float (B, 3, H, W) -> the uint8 (B, H, W, 3) it came from."""
    x = images_nchw.astype(np.float64).transpose(0, 2, 3, 1)
    px = (x * np.asarray(ref_model.STD) + np.asarray(ref_model.MEAN)) * 255.0
    return np.clip(np.rint(px), 0, 255).astype(np.uint8)


def batch_mismatch(prog, ref) -> int:
    """Rows that differ, over (images NCHW float, labels) against
    (images NHWC uint8, labels)."""
    if len(prog) != len(ref):
        return sum(len(rlab) for _, rlab in ref)
    bad = 0
    for (img, lab), (rimg, rlab) in zip(prog, ref):
        if img.shape[0] != rimg.shape[0]:
            bad += len(rlab)
            continue
        u8 = recover_u8(img)
        rows = (u8.reshape(len(u8), -1) != rimg.reshape(len(rimg), -1)).any(1)
        bad += int((rows | (np.asarray(lab) != np.asarray(rlab))).sum())
    return bad


def compare(prog: Dict, ref: Dict, config: Dict) -> Dict[str, float]:
    """The training step's numbers (``bench.check.train_numbers``) and:

    * ``batch_mismatch`` -- rows (image or label) of the first three batches
      that differ from the reference loader's. The step's input is the
      normalised float32 batch; its uint8 pixels are recovered exactly by
      inverting the normalisation and rounding. Exact: limit 0.
    * ``ingest_err`` -- the largest absolute gap between the ingest kernel's
      output (the batch the step got) and a plain normalisation of the
      reference loader's pixels (``ref["normalized"]``)."""
    out = {
        "batch_mismatch": float(batch_mismatch(prog["batches"], ref["batches"])),
        "ingest_err": max(check.max_abs(b[0], n)
                          for b, n in zip(prog["batches"], ref["normalized"])),
    }
    out.update(check.train_numbers(prog, ref, float(config["train"]["beta1"])))
    return out


def flops_per_sample(config: Dict) -> float:
    return flops.train_flops_per_image(config)


def ingest_bytes_per_sample(config: Dict) -> float:
    return flops.ingest_bytes_per_image(config)


# -- calibration (bench/calibrate.py) -------------------------------------------


def leaf_names(config: Dict) -> List[str]:
    import jax

    shapes = jax.eval_shape(lambda k: ref_model.init_params(
        k, tuple(config["resnet_blocks"]), int(config["resnet_width"]),
        int(config["num_classes"])), jax.random.PRNGKey(0))
    return [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(shapes)[0]]


def program_highest(config: Dict, seed: int, ref: Dict) -> Dict:
    """The program's own step at ``highest`` precision on the reference's
    batches: a witness of where the program's gaps come from."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        state = init_state(config, seed)
        p0 = check.host_leaves(state["params"])
        step = jax.jit(make_step(config))
        losses, mu1 = [], None
        for i, (images, (_, labels)) in enumerate(zip(ref["normalized"], ref["batches"])):
            state, m = step(state, {"image": jnp.asarray(images), "label": jnp.asarray(labels)})
            losses.append(float(m["loss"]))
            if i == 0:
                mu1 = check.host_leaves(state["opt"]["mu"])
        return {"losses": losses, "mu1": mu1, "p0": p0, "p3": check.host_leaves(state["params"])}


def readings(config: Dict, seed: int, ref: Dict, prog: Dict) -> Dict:
    """``compare`` of the program and, on the same reference batches, of

    * ``control`` -- the reference computed in bfloat16, put in the
      program's place (the step a later change would be tempted to take);
    * ``control_step`` -- the same with the ingest kept in float32: only the
      step runs in bfloat16, so only the step's numbers can catch it;
    * ``half`` -- the reference stepping on half of each batch;
    * ``unchanged`` -- a step that leaves the state as it was;
    * ``program_highest`` -- see :func:`program_highest`.

    Each is also compared, under ``vs_config``, with the reference run at
    the matmul precision the configuration states (``matmul_precision``),
    as the program runs: against it the program's own precision drops out.
    ``leaves`` names the worst leaf of the gradient and update gaps."""
    import jax.numpy as jnp

    beta1 = float(config["train"]["beta1"])
    names = leaf_names(config)
    details = {}

    def detail(key, like):
        grad, update = check.gap_leaves(like, ref, beta1)
        details[key] = {"grad_worst": names[int(np.nanargmax(grad))],
                        "update_worst": names[int(np.nanargmax(update))]}

    def in_place(out, batches_from=None):
        """A reference run put in the program's place; its batches are its
        own normalisation, or ``batches_from``'s."""
        normalized = (batches_from or out)["normalized"]
        return {"batches": [(n, b[1]) for n, b in zip(normalized, ref["batches"])],
                "losses": out["losses"], "mu1": [g * (1 - beta1) for g in out["g1"]],
                "p0": out["p0"], "p3": out["p3"]}

    planted = {"program": prog}
    for key, ingest in (("control", jnp.bfloat16), ("control_step", jnp.float32)):
        planted[key] = in_place(reference_steps(
            config, ref["batches"], seed, dtype=jnp.bfloat16, ingest_dtype=ingest))
    planted["half"] = in_place(reference_steps(
        config, ref["batches"], seed, rows=samples_per_step(config) // 2), ref)
    faithful = {"batches": planted["half"]["batches"]}
    planted["program_highest"] = dict(faithful, **program_highest(config, seed, ref))
    for key in ("program", "control", "control_step", "program_highest"):
        detail(key, planted[key])
    ref_cfg = dict(reference_steps(config, ref["batches"], seed,
                                   precision=config["matmul_precision"]),
                   batches=ref["batches"])
    vs_config = {k: compare(v, ref_cfg, config) for k, v in planted.items()}
    rows = {k: compare(v, ref, config) for k, v in planted.items()}
    rows["unchanged"] = compare(dict(
        faithful, losses=[ref["losses"][0]] * len(ref["losses"]),
        mu1=[np.zeros_like(g) for g in ref["g1"]], p0=ref["p0"], p3=ref["p0"]), ref, config)
    return {**rows, "leaves": details, "vs_config": vs_config}
