"""Seeded CIFAR-10-format files, read by the launcher's own loader.

Copied from ``chip_smoke.py:write_cifar10_batches`` (five train batches
and a test batch of uint8 ``[N, 3072]`` under ``b"data"`` with
``b"labels"``, pickled as ``cifar-10-batches-py/<name>``) and made fast
enough to run in every set-up: the noise is drawn as bytes and mixed with
the per-class template in integer arithmetic, and every class has the
same count, so the sizes of what the partitioner cuts never depend on
the seed. A pure function of ``seed``.
"""
from __future__ import annotations

import os
import pickle

import numpy as np

NUM_CLASSES = 10
ROW = 3072  # 3 x 32 x 32, channel-major as CIFAR stores it


def _batch(rng, templates, n):
    y = rng.permutation(np.arange(n, dtype=np.int64) % NUM_CLASSES)
    noise = np.frombuffer(rng.bytes(n * ROW), np.uint8).reshape(n, ROW)
    # template in [48, 208) and noise in [0, 256): the halves cannot
    # overflow a byte, and the class signal survives the noise
    x = (templates[y] >> 1) + (noise >> 1)
    return x, y


def write(root: str, seed: int, train_images: int, test_images: int) -> str:
    """Write the files under ``root`` and return the ``--data_dir``."""
    if train_images % 5:
        raise ValueError("train_images must divide into CIFAR's 5 batches")
    base = os.path.join(root, "cifar-10-batches-py")
    os.makedirs(base, exist_ok=True)
    rng = np.random.default_rng(seed)
    templates = rng.integers(48, 208, size=(NUM_CLASSES, ROW),
                             dtype=np.uint8)
    names = [f"data_batch_{i}" for i in range(1, 6)] + ["test_batch"]
    for name in names:
        n = test_images if name == "test_batch" else train_images // 5
        x, y = _batch(rng, templates, n)
        with open(os.path.join(base, name), "wb") as f:
            pickle.dump({b"data": x, b"labels": y.tolist()}, f,
                        protocol=pickle.HIGHEST_PROTOCOL)
    return root
