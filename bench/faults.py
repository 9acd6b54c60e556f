"""Faults planted under the timed path, for the tests that show the check
catching them (``bench/tests/test_faults.py``). A benchmark run never plants
one unless asked to with the hidden ``--fault`` option.

* ``unchanged`` -- the step returns its state unchanged.
* ``half`` -- the step sees half of the batch; the mean runs over the rest.
* ``alter`` -- the loader alters one pixel of some samples where it makes them.
"""
from __future__ import annotations

import numpy as np

from repro.data.dataset import ImageDataset


class AlteredDataset(ImageDataset):
    def augment_item(self, decoded, index):
        item = super().augment_item(decoded, index)
        if index % 5 == 0:
            img = np.array(item["image"])
            img.flat[0] ^= 1
            item["image"] = img
        return item


def wrap_step(fault: str, step):
    if not fault or fault == "alter":
        return step
    if fault == "unchanged":
        def unchanged(state, batch):
            _, metrics = step(state, batch)
            return state, metrics
        return unchanged
    if fault == "half":
        def half(state, batch):
            n = next(iter(batch.values())).shape[0] // 2
            return step(state, {k: v[:n] for k, v in batch.items()})
        return half
    raise ValueError(f"unknown fault {fault!r}")
