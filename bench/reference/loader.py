"""Plain reference loader: the batches the training step must receive.

It walks the sampler's epoch permutation batch by batch, reads each key
through the benchmark's own store, decodes the RIMG record, applies the
random-resized-crop and flip, and stacks. No threads, no processes, no
shared memory: every step is written out from the published behaviour of
the sampler (a seeded permutation of the keyspace), the record format and
torchvision's crop, and none of it is imported from the program.
"""
from __future__ import annotations

import hashlib
import struct
import zlib
from typing import List, Tuple

import numpy as np


def _rng(tag: str) -> np.random.Generator:
    h = hashlib.blake2b(tag.encode(), digest_size=8).digest()
    return np.random.default_rng(int.from_bytes(h, "little"))


def epoch_permutation(n: int, seed: int, epoch: int) -> np.ndarray:
    return _rng(f"sampler:{seed}:{epoch}").permutation(n)


def item_key(index: int, prefix: str) -> str:
    return f"{prefix}{index:08d}.rimg"


def decode(record: bytes) -> Tuple[np.ndarray, int]:
    """RIMG: magic, then little-endian u32 height, width, channels, label,
    a u8 compressed flag, then the (zlib) uint8 HWC payload."""
    if record[:4] != b"RIMG":
        raise ValueError("not an RIMG record")
    h, w, c, label, compressed = struct.unpack("<IIIIB", record[4:21])
    payload = zlib.decompress(record[21:]) if compressed else record[21:]
    return np.frombuffer(payload, np.uint8).reshape(h, w, c), label


def crop_flip(img: np.ndarray, rng: np.random.Generator, out: int) -> np.ndarray:
    """Random resized crop (scale 0.08-1, ratio 3/4-4/3, ten tries, then a
    centre square), nearest-neighbour resize to out x out, then a horizontal
    flip with probability one half."""
    h, w = img.shape[:2]
    full = h * w
    crop = None
    for _ in range(10):
        area = rng.uniform(0.08, 1.0) * full
        r = np.exp(rng.uniform(np.log(3 / 4), np.log(4 / 3)))
        cw, ch = int(round(np.sqrt(area * r))), int(round(np.sqrt(area / r)))
        if 0 < cw <= w and 0 < ch <= h:
            y0 = int(rng.integers(0, h - ch + 1))
            x0 = int(rng.integers(0, w - cw + 1))
            crop = img[y0:y0 + ch, x0:x0 + cw]
            break
    if crop is None:
        side = min(h, w)
        y0, x0 = (h - side) // 2, (w - side) // 2
        crop = img[y0:y0 + side, x0:x0 + side]
    ch, cw = crop.shape[:2]
    yi = (np.arange(out) * (ch / out)).astype(np.int64)
    xi = (np.arange(out) * (cw / out)).astype(np.int64)
    res = crop[yi[:, None], xi[None, :]]
    if rng.random() < 0.5:
        res = res[:, ::-1]
    return np.ascontiguousarray(res)


def batches(store, *, keyspace: int, batch: int, count: int, sampler_seed: int,
            aug_seed: int, out: int, prefix: str) -> List[Tuple[np.ndarray, np.ndarray]]:
    """The first ``count`` batches of epoch 0 as (uint8 NHWC images, int32
    labels)."""
    perm = epoch_permutation(keyspace, sampler_seed, 0)
    result = []
    for b in range(count):
        imgs, labels = [], []
        for index in perm[b * batch:(b + 1) * batch]:
            index = int(index)
            px, label = decode(store.get(item_key(index, prefix)))
            imgs.append(crop_flip(px, _rng(f"aug:{aug_seed}:0:{index}"), out))
            labels.append(label)
        result.append((np.stack(imgs), np.asarray(labels, np.int32)))
    return result
