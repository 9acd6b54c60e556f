"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch granite-8b --smoke \
        --store s3sim --loader threaded --steps 50

Wires the full stack together: object store (simulated-S3 or in-memory
scratch) -> Dataset -> ConcurrentDataLoader (the paper's loader) -> device
prefetch ring -> jitted train step -> Trainer with checkpointing, and prints
the paper's Table-3 columns (throughput + accelerator busy stats) at the end.

``--arch resnet18-imagenet`` trains the paper's own model on the synthetic
ImageNet; every other arch trains on packed token sequences streamed through
the same loader.  ``--smoke`` (default) uses the reduced config so the run
fits a CPU host; ``--full`` lowers the real config (use on real hardware).

:func:`run` is the same driver for callers in the same process (it takes an
argv list and extra trainer callbacks, and hands back the loader and the
trainer); ``chip_smoke.py`` drives the paper's path on a TPU through it.
"""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass
from typing import Any, List, Optional, Sequence

import jax
import jax.random as jr
import numpy as np

from repro.config import (
    AutotuneConfig,
    CacheConfig,
    DeliverySpec,
    LoaderConfig,
    PipelineConfig,
    StoreConfig,
    TrainConfig,
    get_arch,
)
from repro.core import make_loader
from repro.core.tracing import Tracer
from repro.core.utilization import accelerator_stats
from repro.data.dataset import ImageDataset, TokenDataset, build_token_store
from repro.data.imagenet_synth import build_synthetic_imagenet
from repro.data.store import build_store
from repro.launch.compile_cache import enable_compilation_cache
from repro.train.checkpoint import CheckpointManager
from repro.train.steps import (
    init_resnet_train_state,
    init_train_state,
    make_resnet_train_step,
    make_train_step,
)
from repro.train.trainer import (
    Callback,
    CheckpointCallback,
    LoggingCallback,
    Trainer,
)


def build_dataset(cfg, args, tracer):
    """Materialize a synthetic dataset behind the requested store stack."""
    scfg = StoreConfig(
        kind=args.store,
        latency_mean_s=args.latency,
        cache=CacheConfig(memory_bytes=args.cache_mb * 1 << 20),
    )
    if cfg.family == "resnet":
        base = build_synthetic_imagenet(num_items=args.items, avg_kb=48.0)
        store = build_store(scfg, base=base)
        return ImageDataset(
            store, args.items, out_size=cfg.image_size, tracer=tracer,
            sim_decode_s_per_mb=0.052,
            epilogue="device" if getattr(args, "device_ingest", False) else "host",
        )
    seq = args.seq_len
    from repro.data.store import InMemoryStore

    base = InMemoryStore()
    build_token_store(base, args.items, seq, cfg.vocab_size)
    store = build_store(scfg, base=base)
    return TokenDataset(store, args.items, seq, tracer=tracer)


def build_loader(cfg, args, tracer, mesh=None):
    """The loader ``args`` describe, over :func:`build_dataset`; ``mesh``
    switches to sharded delivery along ``args.delivery_axis``."""
    delivery = (DeliverySpec.host() if mesh is None
                else DeliverySpec.sharded(mesh, axis=args.delivery_axis))
    return make_loader(
        LoaderConfig(
            impl=args.loader,
            batch_size=args.batch_size,
            num_workers=args.workers,
            num_fetch_workers=args.fetchers,
            hedge_requests=args.hedge,
            pipeline=PipelineConfig(
                enabled=args.pipeline or mesh is not None,
                reorder=args.reorder,
                reorder_window=args.reorder_window,
                io_workers=args.io_workers,
                cpu_workers=args.cpu_workers,
                cpu_executor=args.cpu_executor,
                transport=args.transport,
                staging_buffers=args.staging_buffers,
            ),
            delivery=delivery,
            autotune=AutotuneConfig(
                enabled=args.autotune or args.thread_budget > 0,
                thread_budget=args.thread_budget,
            ),
            seed=args.seed,
        ),
        build_dataset(cfg, args, tracer),
        tracer=tracer,
    )


@dataclass
class TrainRun:
    """What :func:`run` built and measured."""

    loader: Any
    trainer: Trainer


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--epochs", type=int, default=100)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--items", type=int, default=512)
    ap.add_argument("--store", choices=["memory", "s3sim"], default="s3sim")
    ap.add_argument("--latency", type=float, default=0.02)
    ap.add_argument("--cache-mb", type=int, default=0)
    ap.add_argument("--loader", choices=["vanilla", "threaded", "asyncio"],
                    default="threaded")
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--fetchers", type=int, default=16)
    ap.add_argument("--hedge", action="store_true",
                    help="hedged requests (straggler mitigation)")
    ap.add_argument("--pipeline", action="store_true",
                    help="staged streaming pipeline (fetch/decode/augment on "
                         "dedicated IO+CPU executors)")
    ap.add_argument("--reorder", choices=["strict", "window"], default="strict",
                    help="pipeline batch assembly: strict (bit-identical "
                         "stream) or window (first-N-ready composition)")
    ap.add_argument("--reorder-window", type=int, default=4)
    ap.add_argument("--io-workers", type=int, default=0,
                    help="pipeline IO executor width (0 = adapt to observed "
                         "GET latency, from workers*fetchers up)")
    ap.add_argument("--cpu-workers", type=int, default=0,
                    help="pipeline CPU executor width (0 = 4)")
    ap.add_argument("--cpu-executor", choices=["thread", "process"],
                    default="thread",
                    help="pipeline decode+augment executor: 'thread' (GIL-"
                         "releasing C decoders) or 'process' (spawn pool — "
                         "the GIL escape for Python-side decoders; needs a "
                         "picklable split-path dataset)")
    ap.add_argument("--transport", choices=["pipe", "shm"], default="pipe",
                    help="process CPU stage result transport: 'pipe' "
                         "(pickle both ways) or 'shm' (zero-copy shared-"
                         "memory slabs; only meaningful with "
                         "--cpu-executor process)")
    ap.add_argument("--staging-buffers", type=int, default=0,
                    help="pinned host staging: collate into this many "
                         "reusable page-aligned buffer sets per consumer "
                         "(0 = plain np.stack collate)")
    ap.add_argument("--device-ingest", action="store_true",
                    help="resnet only: host stages stop at raw uint8 HWC "
                         "and the fused kernels/ingest_norm epilogue runs "
                         "cast+normalize on device after H2D (4x fewer "
                         "host-side bytes per image)")
    ap.add_argument("--delivery", choices=["host", "sharded"], default="host",
                    help="batch delivery: 'host' (one host array, consumer "
                         "re-shards) or 'sharded' (per-mesh-slice assembler "
                         "lanes compose a device-sharded global batch; "
                         "requires --pipeline)")
    ap.add_argument("--delivery-axis", default="data",
                    help="mesh axis the batch dim is sharded over")
    ap.add_argument("--autotune", action="store_true",
                    help="online knob control (closed-loop io/cpu/queue/"
                         "outstanding tuning)")
    ap.add_argument("--thread-budget", type=int, default=0,
                    help="co-tune the pipeline io/cpu split (and executor "
                         "kind) as ONE knob under this fixed total width; "
                         "implies --autotune (0 = independent knobs)")
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compression", default="none",
                    choices=["none", "bf16", "int8_ef"])
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def run(argv: Optional[Sequence[str]] = None,
        callbacks: Sequence[Callback] = ()) -> TrainRun:
    """Build the store -> loader -> step stack from ``argv`` and train.
    ``callbacks`` join the trainer's own (logging, checkpointing)."""
    args = parse_args(argv)
    cache_dir = enable_compilation_cache()
    dev = jax.devices()[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={jax.device_count()} compile_cache={cache_dir}", flush=True)

    cfg = get_arch(args.arch, smoke=args.smoke)
    tcfg = TrainConfig(
        optimizer=args.optimizer,
        learning_rate=args.lr,
        microbatches=args.microbatches,
        grad_compression=args.grad_compression,
        total_steps=args.steps,
    )
    tracer = Tracer()
    mesh = None
    if args.delivery == "sharded":
        # one lane per local device along the data axis; multi-host runs
        # pass a jax.distributed mesh here instead
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((jax.device_count(),), (args.delivery_axis,))
    loader = build_loader(cfg, args, tracer, mesh)

    key = jr.PRNGKey(args.seed)
    if cfg.family == "resnet":
        state = init_resnet_train_state(cfg, tcfg, key)
        step_fn = make_resnet_train_step(cfg, tcfg)
    else:
        state = init_train_state(cfg, tcfg, key)
        step_fn = make_train_step(cfg, tcfg)
    if mesh is not None:
        # data parallel over the delivery mesh: every device holds the whole
        # train state, the batch arrives sharded along the data axis
        from jax.sharding import NamedSharding, PartitionSpec

        state = jax.device_put(state, NamedSharding(mesh, PartitionSpec()))
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(state["params"]))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M "
          f"loader={args.loader} store={args.store}")

    callbacks: List[Callback] = [
        LoggingCallback(log_every_n_steps=args.log_every,
                        sink=lambda s: print("  " + s, flush=True)),
        *callbacks,
    ]
    manager = None
    if args.ckpt_dir:
        manager = CheckpointManager(args.ckpt_dir, keep=3)
        callbacks.append(
            CheckpointCallback(manager, args.ckpt_every, loader=loader)
        )
    ingest_fn = None
    if args.device_ingest:
        if cfg.family != "resnet":
            raise SystemExit("--device-ingest requires an image (resnet) arch")
        from repro.kernels.ingest_norm.ops import make_ingest_fn

        ingest_fn = make_ingest_fn(mesh=mesh, axis=args.delivery_axis)
        print(f"ingest: {ingest_fn.impl} (make_ingest_fn impl=auto on "
              f"{jax.default_backend()})", flush=True)
    trainer = Trainer(step_fn, state, callbacks=callbacks, tracer=tracer,
                      ingest_fn=ingest_fn)

    start_epoch = 0
    if manager is not None and args.resume and manager.latest_step() is not None:
        trainer.state, meta = manager.restore(trainer.state)
        trainer.global_step = int(meta.get("step", 0))
        if "loader" in meta.get("extra", {}):
            loader.load_state_dict(meta["extra"]["loader"])
            start_epoch = loader.state_dict()["epoch"]
        print(f"resumed from step {trainer.global_step}")

    t0 = time.monotonic()
    result = trainer.fit(
        loader, epochs=args.epochs, max_steps=args.steps, start_epoch=start_epoch
    )
    t1 = time.monotonic()
    if manager is not None:
        manager.wait()

    util = accelerator_stats(tracer, t0, t1)
    items = result.steps * args.batch_size
    print(
        f"\nsteps={result.steps} wall={result.wall_s:.1f}s "
        f"items/s={items / result.wall_s:.1f} "
        f"loss={result.last_metrics.get('loss', float('nan')):.4f}"
    )
    # a host-clock proxy over run_training_batch spans, not a device trace
    print(
        f"host-span proxy (run_training_batch spans): "
        f"util_zero={util.util_zero_pct:.1f}% "
        f"util_pos_avg={util.util_pos_avg:.1f}% busy={100 * util.busy_fraction:.1f}%"
    )
    stages = loader.stage_stats()
    if stages is not None:
        print(f"pipeline stages: {stages}")
    return TrainRun(loader, trainer)


def main(argv: Optional[Sequence[str]] = None) -> int:
    run(argv)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
