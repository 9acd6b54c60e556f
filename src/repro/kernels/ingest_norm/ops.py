"""Jit-able wrappers for fused ingest.

``ingest_norm`` is the raw array op (u8 NHWC -> normalized f32 NCHW).
``make_ingest_fn`` packages it as the batch-level epilogue the training loop
hands to :class:`repro.core.prefetch.DevicePrefetchRing`: a jitted
dict -> dict callable that replaces a uint8 HWC ``image`` with the
normalized CHW tensor the model expects, leaving every other key (and any
batch that already arrived as f32 from the host epilogue) untouched.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ingest_norm.kernel import ingest_norm_batched
from repro.kernels.ingest_norm.ref import ingest_norm_ref


@functools.partial(jax.jit, static_argnames=("interpret",))
def ingest_norm(img_u8, mean, std, interpret: bool = False):
    return ingest_norm_batched(img_u8, mean, std, interpret=interpret)


def make_ingest_fn(
    mean: Optional[Any] = None,
    std: Optional[Any] = None,
    *,
    key: str = "image",
    out_dtype: Any = jnp.float32,
    impl: str = "auto",
    interpret: bool = False,
    mesh: Optional[Any] = None,
    axis: str = "data",
) -> Any:
    """Build the on-device ingest epilogue for ``DevicePrefetchRing``.

    ``mean``/``std`` default to the ImageNet constants (matching the host
    :func:`repro.data.augment.to_tensor_normalize`).  ``impl`` picks the
    kernel: ``"pallas"`` (the fused fma kernel), ``"ref"`` (pure jnp, what
    XLA fuses on CPU/GPU), or ``"auto"`` (pallas on TPU, ref elsewhere —
    interpret-mode pallas would serialize the grid on CPU).  The choice is
    recorded on the returned callable as ``.impl`` so a caller can say
    which kernel ran.

    ``mesh`` is for batches sharded along ``axis`` of a device mesh (sharded
    delivery): a Pallas kernel cannot be partitioned by the compiler, so it
    then runs under ``shard_map``, each device on its own rows.

    The returned callable is safe to apply to any batch dict: it only
    rewrites ``key`` when it finds a uint8 NHWC array, so host-epilogue
    batches and non-image pipelines pass through unchanged (the dtype check
    happens at trace time — no device-side branching).

    Its program is ``jit_ingest`` and the kernel sits under the ``ingest``
    scope: the names by which a profiler trace's reader finds it.
    """
    if impl not in ("auto", "pallas", "ref"):
        raise ValueError(f"impl must be auto|pallas|ref, got {impl!r}")
    if mean is None or std is None:
        from repro.data.augment import IMAGENET_MEAN, IMAGENET_STD

        mean = IMAGENET_MEAN if mean is None else mean
        std = IMAGENET_STD if std is None else std
    mean = np.asarray(mean, dtype=np.float32)
    std = np.asarray(std, dtype=np.float32)
    use_pallas = impl == "pallas" or (
        impl == "auto" and jax.default_backend() == "tpu"
    )

    def norm(img):
        if use_pallas:
            return ingest_norm_batched(
                img, jnp.asarray(mean), jnp.asarray(std), out_dtype=out_dtype,
                interpret=interpret,
            )
        return ingest_norm_ref(img, jnp.asarray(mean), jnp.asarray(std),
                               out_dtype=out_dtype)

    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        norm = jax.shard_map(norm, mesh=mesh, in_specs=P(axis),
                             out_specs=P(axis), check_vma=False)

    @jax.jit
    def ingest(batch: Dict[str, Any]) -> Dict[str, Any]:
        img = batch.get(key) if hasattr(batch, "get") else None
        if img is None or img.dtype != jnp.uint8 or img.ndim != 4:
            return dict(batch) if isinstance(batch, dict) else batch
        new = dict(batch)
        with jax.named_scope("ingest"):
            new[key] = norm(img)
        return new

    ingest.impl = "pallas" if use_pallas else "ref"
    return ingest
