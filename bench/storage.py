"""The traffic generator: a pool of objects behind a keyspace, served from
memory or through a model of remote object storage.

Everything here is driven by a traffic file (``bench/traffic/<mix>.json``):

* ``objects`` -- how the pool is made, which the cell's family reads
  (``bench/families/<family>.py``, ``load_pool``). For images
  (:func:`load_pool`): RIMG records of ``height`` x ``width`` x 3 uint8
  pixels, a posterised smooth field with sparse noise, zlib-compressed.
  ``noise_p`` sets how much of the image is noise and so the stored size.
  That pool is made once per checkout from ``pool_seed`` and kept under
  ``bench/.pool/``; later runs read it back. A family whose samples are
  made quickly builds its pool in memory (:meth:`Pool.of`).
* ``keyspace`` -- how many keys the sampler walks. Key ``i`` (after the
  family's prefix) is served by pool object ``slot(i)``, a mapping drawn
  from the run's seed.
* ``storage`` -- ``{"kind": "local"}`` serves from the host's memory;
  ``{"kind": "s3", ...}`` adds, per GET, a connection-pool wait, a lognormal
  latency and a transfer time at ``min(bandwidth_per_conn, nic_bandwidth /
  GETs in flight)``. The latency is drawn from (seed, key, attempt), so a
  run repeats exactly. The arithmetic is that of the paper's calibration
  (median 80 ms, sigma 0.5, 25 MB/s per connection, 1.2 GB/s NIC).
"""
from __future__ import annotations

import hashlib
import json
import multiprocessing
import os
import random
import struct
import threading
import time
import zlib
from typing import Dict, Sequence

import numpy as np

POOL_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), ".pool")
PREFIX = "imagenet/train/"


def encode(pixels: np.ndarray, label: int, level: int) -> bytes:
    """An RIMG record: magic, u32 height, width, channels, label, a u8
    compressed flag, and the zlib-compressed HWC payload."""
    h, w, c = pixels.shape
    return (b"RIMG" + struct.pack("<IIIIB", h, w, c, label, 1)
            + zlib.compress(pixels.tobytes(), level))


def make_object(spec: Dict, index: int) -> bytes:
    """Pool object ``index``: a coarse random colour grid, bilinearly
    upsampled, posterised, plus sparse uniform noise."""
    rng = np.random.default_rng([int(spec["pool_seed"]), index])
    h, w, g = int(spec["height"]), int(spec["width"]), int(spec["coarse"])
    grid = rng.integers(0, 256, size=(g, g, 3)).astype(np.float32)
    yi, xi = np.linspace(0, g - 1, h), np.linspace(0, g - 1, w)
    y0 = np.floor(yi).astype(int).clip(0, g - 2)
    x0 = np.floor(xi).astype(int).clip(0, g - 2)
    fy, fx = (yi - y0)[:, None, None], (xi - x0)[None, :, None]
    top = grid[y0][:, x0] * (1 - fx) + grid[y0][:, x0 + 1] * fx
    bottom = grid[y0 + 1][:, x0] * (1 - fx) + grid[y0 + 1][:, x0 + 1] * fx
    q = float(spec["posterize"])
    base = np.floor((top * (1 - fy) + bottom * fy) / q) * q
    lo, hi = spec["noise_p"]
    p = rng.uniform(lo, hi)
    noise = rng.integers(0, int(spec["noise_amp"]), size=(h, w, 3)) * (rng.random((h, w, 3)) < p)
    px = np.clip(base + noise, 0, 255).astype(np.uint8)
    return encode(px, int(rng.integers(0, 1000)), int(spec["zlib_level"]))


def _make_object_star(args):
    return make_object(*args)


class Pool:
    """The pool's objects in one buffer, with their offsets."""

    def __init__(self, blob: bytes, offsets: np.ndarray) -> None:
        self.blob = blob
        self.offsets = offsets

    @classmethod
    def of(cls, objects: Sequence[bytes]) -> "Pool":
        offsets = np.zeros(len(objects) + 1, np.int64)
        offsets[1:] = np.cumsum([len(o) for o in objects])
        return cls(b"".join(objects), offsets)

    def __len__(self) -> int:
        return len(self.offsets) - 1

    def get(self, i: int) -> bytes:
        return self.blob[self.offsets[i]:self.offsets[i + 1]]

    def mean_size(self) -> float:
        return float(np.diff(self.offsets).mean())


def load_pool(spec: Dict, pool_dir: str = POOL_DIR, workers: int = 0) -> Pool:
    """Read the pool that ``spec`` describes from ``pool_dir``, making it
    first (in ``workers`` processes) if it is not there yet."""
    digest = hashlib.blake2b(json.dumps(spec, sort_keys=True).encode(),
                             digest_size=8).hexdigest()
    path = os.path.join(pool_dir, f"pool-{digest}.bin")
    if not os.path.exists(path):
        n = int(spec["pool"])
        workers = workers or min(n, os.cpu_count() or 1, 16)
        jobs = [(spec, i) for i in range(n)]
        if workers > 1:
            with multiprocessing.get_context("spawn").Pool(workers) as mp:
                objs = mp.map(_make_object_star, jobs, chunksize=16)
        else:
            objs = [make_object(*j) for j in jobs]
        pool = Pool.of(objs)
        os.makedirs(pool_dir, exist_ok=True)
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "wb") as f:
            f.write(struct.pack("<Q", n))
            f.write(pool.offsets.tobytes())
            f.write(pool.blob)
        os.replace(tmp, path)
    with open(path, "rb") as f:
        (n,) = struct.unpack("<Q", f.read(8))
        offsets = np.frombuffer(f.read(8 * (n + 1)), np.int64)
        blob = f.read()
    return Pool(blob, offsets)


class PoolStore:
    """Local scratch: every key of the keyspace answered from the pool in
    memory, with no latency model."""

    def __init__(self, pool: Pool, keyspace: int, seed: int, prefix: str = PREFIX) -> None:
        self.pool = pool
        self.keyspace = keyspace
        self.prefix = prefix
        self._mix = int.from_bytes(
            hashlib.blake2b(f"slot:{seed}".encode(), digest_size=8).digest(), "little")

    def slot(self, index: int) -> int:
        return ((index * 0x9E3779B97F4A7C15) ^ self._mix) % len(self.pool)

    def get(self, key: str) -> bytes:
        if not key.startswith(self.prefix):
            raise KeyError(key)
        index = int(key[len(self.prefix):].split(".")[0])
        if not 0 <= index < self.keyspace:
            raise KeyError(key)
        return bytes(self.pool.get(self.slot(index)))


class S3Model:
    """Remote object storage in front of ``base``: connection pool, lognormal
    latency and shared bandwidth, as the module docstring says."""

    def __init__(self, base, *, latency_median_s: float, latency_sigma: float,
                 bandwidth_per_conn: float, nic_bandwidth: float,
                 max_connections: int, seed: int) -> None:
        self.base = base
        self.latency_median_s = latency_median_s
        self.latency_sigma = latency_sigma
        self.bandwidth_per_conn = bandwidth_per_conn
        self.nic_bandwidth = nic_bandwidth
        self.seed = seed
        self._sem = threading.BoundedSemaphore(max_connections)
        self._lock = threading.Lock()
        self._active = 0
        self._attempts: Dict[str, int] = {}

    def latency(self, key: str, attempt: int) -> float:
        h = hashlib.blake2b(f"{self.seed}:{key}:{attempt}".encode(), digest_size=8).digest()
        rng = random.Random(int.from_bytes(h, "little"))
        return rng.lognormvariate(0.0, self.latency_sigma) * self.latency_median_s

    def service_time(self, key: str, attempt: int, size: int, active: int) -> float:
        bw = min(self.bandwidth_per_conn, self.nic_bandwidth / max(active, 1))
        return self.latency(key, attempt) + size / bw

    def get(self, key: str) -> bytes:
        with self._sem:
            with self._lock:
                self._active += 1
                active = self._active
                attempt = self._attempts.get(key, 0)
                self._attempts[key] = attempt + 1
            try:
                data = self.base.get(key)
                time.sleep(self.service_time(key, attempt, len(data), active))
                return data
            finally:
                with self._lock:
                    self._active -= 1


def build_store(traffic: Dict, pool: Pool, seed: int, prefix: str = PREFIX):
    """The store a traffic file describes, over ``pool``, answering the keys
    under ``prefix``."""
    base = PoolStore(pool, int(traffic["keyspace"]), seed, prefix)
    st = dict(traffic["storage"])
    kind = st.pop("kind")
    if kind == "local":
        return base
    if kind == "s3":
        return S3Model(base, seed=seed, **st)
    raise ValueError(f"unknown storage kind {kind!r}")
