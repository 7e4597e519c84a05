"""Seeded synthetic stand-ins for MNIST and STL-10, with the datasets' shapes.

A copy of the program's generator (``repro.data.synthetic.make_image_classes``
and ``repro.data.coding.complementary_code``), kept here so that no change to
the program can change the benchmark's inputs.  Each sample is one of
``prototypes_per_class`` class prototypes plus Gaussian noise on the
informative features; the remaining features are uniform noise shared by all
classes.  Values lie in [0, 1].
"""
from __future__ import annotations

import numpy as np


def image_classes(n: int, n_features: int, seed: int, n_classes: int = 10,
                  prototypes_per_class: int = 4, noise: float = 0.15,
                  informative_fraction: float = 0.5):
    """``(x, y)``: ``n`` float32 rows in [0, 1]^n_features and int32 labels."""
    rng = np.random.default_rng(seed)
    n_info = max(1, int(n_features * informative_fraction))
    protos = rng.random((n_classes, prototypes_per_class, n_info),
                        dtype=np.float32)
    y = rng.integers(0, n_classes, size=n).astype(np.int32)
    p = rng.integers(0, prototypes_per_class, size=n)
    x = np.empty((n, n_features), np.float32)
    x[:, :n_info] = protos[y, p]
    x[:, :n_info] += rng.standard_normal((n, n_info), dtype=np.float32) * noise
    x[:, n_info:] = rng.random((n, n_features - n_info), dtype=np.float32)
    np.clip(x, 0.0, 1.0, out=x)
    return x, y


def complementary_code(x: np.ndarray) -> np.ndarray:
    """``(n, F)`` in [0, 1] -> ``(n, 2F)``: each feature becomes a two-unit
    hypercolumn ``(x, 1 - x)``."""
    n, f = x.shape
    out = np.empty((n, 2 * f), np.float32)
    out[:, 0::2] = x
    out[:, 1::2] = 1.0 - x
    return out


def make_inputs(cfg: dict, seed: int):
    """The training split a configuration names: ``(x, y)`` with ``x`` the
    complementary-coded host array a user hands to ``fit``."""
    x, y = image_classes(
        cfg["n_train"], cfg["n_features"], seed,
        n_classes=cfg["n_classes"],
        informative_fraction=cfg["informative_fraction"],
    )
    return complementary_code(x), y
