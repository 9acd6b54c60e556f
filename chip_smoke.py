"""Smoke run of the paper's training path on a TPU.

    python chip_smoke.py              # one chip: ingest check + train phase
    python chip_smoke.py --chips 4    # four chips: sharded delivery only
    python chip_smoke.py --rehearse   # CPU, tiny sizes, Pallas in interpret
                                      # mode (add --chips 4 for 4 virtual
                                      # CPU devices)

Every phase runs in its own child process, one after another, so only one
process at a time holds the chip; this parent never imports jax.  The
phases:

* ``ingest`` -- the Pallas ``ingest_norm`` kernel at 256x224x224x3 uint8:
  its compiled program holds a ``tpu_custom_call``, and its output matches
  ``ingest_norm_ref`` (run on the host CPU) to 1e-5.
* ``train`` -- ``launch/train.py``'s ``run`` with ResNet-18 at published
  widths (``--full``), batch 256 at 224 px, the staged pipeline with
  spawned CPU workers, shm transport, pooled staging and device ingest,
  fed from a low-latency simulated S3 store.  It prints the backend compile
  seconds, the time of each step (to ``block_until_ready``; an observation,
  not a benchmark figure), the loss of each step, the staging pool's
  ``detached`` count and the device's peak memory; and it checks the
  forward loss of the first 16 images of batch 0 on the TPU against the
  host CPU, both at ``highest`` matmul precision.
* ``sharded`` (``--chips 4`` only) -- sharded delivery on a ``(4,)``
  ``data`` mesh against host-batch-then-reshard: the composed batch is
  bit-identical, each chip holds its own rows, the train state is
  replicated, and the first-step losses agree.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``,
printed only when every phase passed on a TPU.  Otherwise the script exits
non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
DEADLINE_S = 1150  # the whole run, compiles included
INGEST_TOL = 1e-5
FORWARD_RTOL = 1e-3
FORWARD_IMAGES = 16
TIMED_STEPS = 6  # after one warm-up (compiling) step


def _sizes(rehearse: bool) -> dict:
    if rehearse:
        return dict(batch=16, image=32, items=16 * (TIMED_STEPS + 1),
                    full=False)
    return dict(batch=256, image=224, items=256 * (TIMED_STEPS + 1),
                full=True)


def _train_argv(rehearse: bool, steps: int, items: int) -> list:
    sz = _sizes(rehearse)
    return [
        "--arch", "resnet18-imagenet", "--full" if sz["full"] else "--smoke",
        "--batch-size", str(sz["batch"]), "--items", str(items),
        "--steps", str(steps), "--epochs", "1", "--log-every", "1",
        "--store", "s3sim", "--latency", "0.002",
        "--pipeline", "--device-ingest",
        "--cpu-executor", "process", "--transport", "shm",
        "--staging-buffers", "2", "--cpu-workers", "8",
    ]


def _log(msg: str) -> None:
    print(msg, flush=True)


# -- phases (each runs in its own child process) ------------------------------


def _start_phase(rehearse: bool, chips: int) -> dict:
    """Common phase prologue: the device this process got, checked, and the
    persistent compilation cache switched on."""
    import jax

    from repro.launch.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    _log(f"device: {dev}")
    if not rehearse and dev["platform"] != "tpu":
        raise SystemExit(f"no TPU: jax found {dev['platform']}")
    if dev["count"] < chips:
        raise SystemExit(f"need {chips} devices, jax found {dev['count']}")
    return dev


def _phase_ingest(rehearse: bool) -> dict:
    import jax
    import numpy as np

    from repro.data.augment import IMAGENET_MEAN, IMAGENET_STD
    from repro.kernels.ingest_norm.ops import make_ingest_fn
    from repro.kernels.ingest_norm.ref import ingest_norm_ref

    sz = _sizes(rehearse)
    rng = np.random.default_rng(0)
    img = rng.integers(0, 256, (sz["batch"], sz["image"], sz["image"], 3),
                       dtype=np.uint8)
    # on the TPU, impl="auto" must pick the kernel by itself
    fn = (make_ingest_fn(impl="pallas", interpret=True) if rehearse
          else make_ingest_fn())
    _log(f"ingest: make_ingest_fn chose {fn.impl}")
    x = jax.device_put(img)
    compiled = fn.lower({"image": x}).compile()
    custom = "tpu_custom_call" in compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    out = np.asarray(jax.block_until_ready(fn({"image": x})["image"]))
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        ref = np.asarray(ingest_norm_ref(
            jax.device_put(img, cpu), np.asarray(IMAGENET_MEAN, np.float32),
            np.asarray(IMAGENET_STD, np.float32)))
    err = float(np.abs(out - ref).max())
    _log(f"ingest: shape={out.shape} tpu_custom_call={custom} "
         f"temp_bytes={temp} max_abs_err_vs_ref={err:.3e}")
    ok = fn.impl == "pallas" and err <= INGEST_TOL and out.shape == ref.shape
    if not rehearse:
        ok = ok and custom
    return {"ok": ok, "tpu_custom_call": custom, "max_abs_err": err}


def _forward_losses(cfg, params, bn, batch) -> tuple:
    """Training-mode forward loss on the default device and on the host CPU,
    from the same host copies of the weights and images."""
    import jax
    import numpy as np

    from repro.models.resnet import resnet_loss

    host = jax.device_get((params, bn, batch))
    loss = jax.jit(lambda p, s, b: resnet_loss(p, s, b, cfg, train=True)[0])
    with jax.default_matmul_precision("highest"):
        on_dev = float(loss(*jax.device_put(host, jax.devices()[0])))
        on_cpu = float(loss(*jax.device_put(host, jax.devices("cpu")[0])))
    rel = abs(on_dev - on_cpu) / max(abs(on_cpu), 1e-12)
    return on_dev, on_cpu, rel if np.isfinite(rel) else float("inf")


def _phase_train(rehearse: bool) -> dict:
    import statistics

    import jax
    import numpy as np

    from repro.config import get_arch
    from repro.launch import train
    from repro.train.trainer import Callback

    steps = TIMED_STEPS + 1
    argv = _train_argv(rehearse, steps, _sizes(rehearse)["items"])
    cfg = get_arch("resnet18-imagenet", smoke=rehearse)

    compile_s, cache_hits = [], []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compile_s.append(secs)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    jax.monitoring.register_event_listener(
        lambda event, **kw: cache_hits.append(event)
        if event == "/jax/compilation_cache/cache_hits" else None)

    class Probe(Callback):
        def __init__(self):
            self.step_s, self.losses, self.forward = [], [], None
            self.batch_spec = None
            self._t0 = 0.0

        def on_train_batch_start(self, trainer, batch, idx):
            if trainer.global_step == 0:
                self.batch_spec = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                   sharding=x.sharding), batch)
                # before the first step: the state is donated to it
                n = FORWARD_IMAGES
                self.forward = _forward_losses(
                    cfg, trainer.state["params"], trainer.state["bn"],
                    {"image": batch["image"][:n], "label": batch["label"][:n]})
            self._t0 = time.perf_counter()

        def on_train_batch_end(self, trainer, metrics, idx):
            jax.block_until_ready(trainer.state)
            self.step_s.append(time.perf_counter() - self._t0)
            self.losses.append(float(metrics["loss"]))

    probe = Probe()
    out = train.run(argv, callbacks=[probe])
    stats = out.loader.stage_stats() or {}
    detached = stats.get("staging", {}).get("detached")
    mem = jax.devices()[0].memory_stats() or {}
    peak = mem.get("peak_bytes_in_use")
    # the compiled step's own accounting, to set beside the runtime's peak
    step_mem = out.trainer.train_step.lower(
        out.trainer.state, probe.batch_spec).compile().memory_analysis()
    timed = probe.step_s[1:]
    median_ms = 1e3 * statistics.median(timed) if timed else float("nan")
    dev_loss, cpu_loss, rel = probe.forward
    _log(f"train: backend compile s={sum(compile_s):.1f} "
         f"(largest {max(compile_s, default=0.0):.1f}, {len(compile_s)} "
         f"programs compiled, {len(cache_hits)} loaded from the persistent "
         f"cache)")
    _log(f"train: first step s={probe.step_s[0]:.2f} (compile + run)")
    _log(f"train: step ms={[round(1e3 * t, 2) for t in timed]} "
         f"median={median_ms:.2f} (smoke observation, not a benchmark)")
    _log(f"train: losses={probe.losses}")
    _log(f"train: ingest={out.trainer.ingest_fn.impl} staging detached="
         f"{detached} peak_bytes_in_use={peak}")
    _log("train: memory_stats " + " ".join(
        f"{k}={mem.get(k)}" for k in ("bytes_in_use", "peak_bytes_in_use",
                                      "largest_alloc_size", "bytes_limit")))
    _log("train: compiled step memory_analysis " + " ".join(
        f"{k}={getattr(step_mem, k + '_size_in_bytes', None)}"
        for k in ("argument", "output", "alias", "temp",
                  "generated_code")))
    _log(f"train: forward loss on {FORWARD_IMAGES} images: "
         f"device={dev_loss:.6f} cpu={cpu_loss:.6f} rel_diff={rel:.3e}")
    ok = (len(timed) >= 5 and len(probe.losses) == steps
          and all(np.isfinite(probe.losses)) and rel <= FORWARD_RTOL)
    if not rehearse:
        # on the TPU the H2D copy is real: no staging lease may alias
        ok = ok and detached == 0 and out.trainer.ingest_fn.impl == "pallas"
    return {"ok": ok, "median_step_ms": median_ms, "losses": probe.losses,
            "detached": detached, "peak_bytes_in_use": peak,
            "compile_s": sum(compile_s), "cache_hits": len(cache_hits),
            "forward_rel_diff": rel}


def _first_batch(loader):
    it = iter(loader)
    batch = next(it)
    it.shutdown()
    return batch


def _replicated(tree, n: int) -> bool:
    import jax

    return all(leaf.sharding.is_fully_replicated
               and len(leaf.sharding.device_set) == n
               for leaf in jax.tree.leaves(tree))


def _phase_sharded(rehearse: bool) -> dict:
    """``train.run --delivery sharded`` on a (devices,) mesh, checked against
    host-batch-then-reshard through the trainer's own ingest and step."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.config import get_arch
    from repro.core.tracing import Tracer
    from repro.launch import train
    from repro.models.sharding import batch_sharding
    from repro.train.trainer import Callback

    argv = (_train_argv(rehearse, 1, _sizes(rehearse)["batch"])
            + ["--delivery", "sharded"])
    args = train.parse_args(argv)
    cfg = get_arch(args.arch, smoke=args.smoke)
    n = jax.device_count()
    host = {k: np.array(v) for k, v in
            _first_batch(train.build_loader(cfg, args, Tracer())).items()}

    class Probe(Callback):
        """At the first step (before it donates the state): compare the
        sharded batch with the host batch resharded and put through the
        same ingest, and run the same jitted step on a copy of the state."""

        def __init__(self):
            self.checks, self.loss_host, self.loss_sharded = {}, None, None

        def on_train_batch_start(self, trainer, batch, idx):
            if idx:
                return
            mesh = batch["image"].sharding.mesh
            ref = {k: jax.device_put(v, batch_sharding(mesh, v.shape))
                   for k, v in host.items()}
            if trainer.ingest_fn is not None:
                ref = trainer.ingest_fn(ref)
            want = {k: np.asarray(v) for k, v in ref.items()}
            # ingest maps u8 to f32 one-to-one per channel, so equal
            # ingested batches mean equal composed u8 batches
            self.checks["batch bit-identical"] = sorted(batch) == sorted(
                want) and all(np.array_equal(np.asarray(batch[k]), want[k])
                              for k in want)
            self.checks["each chip holds its own rows"] = all(
                len({s.device for s in arr.addressable_shards}) == n
                and all(s.data.shape[0] == want[k].shape[0] // n
                        and np.array_equal(np.asarray(s.data), want[k][s.index])
                        for s in arr.addressable_shards)
                for k, arr in batch.items())
            self.checks["state replicated"] = _replicated(trainer.state, n)
            state = jax.tree.map(lambda x: jnp.array(x, copy=True),
                                 trainer.state)
            _, m = trainer.train_step(state, ref)
            self.loss_host = float(m["loss"])

        def on_train_batch_end(self, trainer, metrics, idx):
            if idx == 0:
                self.loss_sharded = float(metrics["loss"])
                self.checks["state replicated after the step"] = _replicated(
                    trainer.state, n)

    probe = Probe()
    out = train.run(argv, callbacks=[probe])
    loss_s, loss_h = probe.loss_sharded, probe.loss_host
    _log(f"sharded: devices={n} ingest={out.trainer.ingest_fn.impl} "
         + " ".join(f"{k.replace(' ', '_')}={v}"
                    for k, v in probe.checks.items()))
    _log(f"sharded: first-step loss sharded={loss_s!r} host-reshard={loss_h!r}")
    ok = (len(probe.checks) == 4 and all(probe.checks.values())
          and loss_s is not None and loss_h is not None
          and np.isfinite(loss_s)
          and abs(loss_s - loss_h) <= 1e-6 * max(abs(loss_h), 1.0))
    return {"ok": bool(ok), **probe.checks, "loss_sharded": loss_s,
            "loss_host": loss_h}


PHASES = {"ingest": _phase_ingest, "train": _phase_train,
          "sharded": _phase_sharded}


def _run_phase(name: str, rehearse: bool, chips: int) -> int:
    dev = _start_phase(rehearse, chips)
    res = PHASES[name](rehearse)
    print(json.dumps({"phase": name, "device": dev, **res}), flush=True)
    return 0 if res["ok"] else 1


# -- parent: runs the phases one after another --------------------------------


def _child_env(rehearse: bool, chips: int) -> dict:
    env = dict(os.environ)
    src = os.path.join(HERE, "src")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (src, env.get("PYTHONPATH", "")) if p)
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        if chips > 1:
            env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={chips} "
                                + env.get("XLA_FLAGS", ""))
    return env


def _spawn(name: str, rehearse: bool, chips: int, timeout_s: float):
    """Run one phase in a child process (in its own process group, so a
    timeout also stops the loader workers it spawned); echo its output and
    return (exit code, its last line parsed as JSON or None)."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", name,
           "--chips", str(chips)] + (["--rehearse"] if rehearse else [])
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True,
                            env=_child_env(rehearse, chips),
                            start_new_session=True)
    timer = threading.Timer(timeout_s, os.killpg, (proc.pid, signal.SIGKILL))
    timer.start()
    last = ""
    try:
        for line in proc.stdout:
            sys.stdout.write(line)
            sys.stdout.flush()
            if line.strip():
                last = line.strip()
        rc = proc.wait()
    finally:
        timer.cancel()
        try:  # whatever the phase left behind
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
    try:
        return rc, json.loads(last)
    except ValueError:
        return rc, None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at tiny sizes; prints no ok line")
    ap.add_argument("--phase", choices=sorted(PHASES), help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.phase:
        return _run_phase(args.phase, args.rehearse, args.chips)

    phases = ("sharded",) if args.chips == 4 else ("ingest", "train")
    end = time.monotonic() + DEADLINE_S
    devices = []
    for name in phases:
        _log(f"=== phase {name} ===")
        rc, res = _spawn(name, args.rehearse, args.chips,
                         max(end - time.monotonic(), 1.0))
        if rc != 0 or not res or res.get("phase") != name or not res.get("ok"):
            _log(f"phase {name} failed (exit code {rc})")
            return 1
        devices.append(res["device"])
    dev = devices[0]
    if any(d != dev for d in devices) or dev["count"] < args.chips:
        _log(f"phases disagree on the device: {devices}")
        return 1
    if args.rehearse:
        _log(json.dumps({"rehearsal": "passed", "device": dev}))
        return 0
    if dev["platform"] != "tpu":
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
