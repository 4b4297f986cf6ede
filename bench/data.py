"""The benchmark's job data, generated on the host.

The generator is the program's `data.make_multiclass` (a c-class Gaussian
mixture with two sub-clusters per class) and `core.kernel_fn.median_gamma`,
frozen here as of the commit that defined the benchmark so that the
yardstick's data cannot move with the program's code, and split in two:
the mixture's geometry, and points drawn from it.

A configuration is one data set, as a published data set is one: its
geometry, its training rows and its landmark key come from the
configuration's ``data_seed``.  ``--seed`` draws the test rows from the
same mixture and the rows the check samples.  The training set is fixed
because the work of a fit to tolerance is a property of the training set:
the slowest task's epochs moved by a quarter or more between data sets,
between row orders of one data set, and between landmark draws (see
PERF.md), so a seed that changed the training set would change the work.
"""
from __future__ import annotations

import numpy as np


def mixture(p: int, n_classes: int, sep: float, within: float,
            data_seed: int):
    """Class centers (c, p) and sub-cluster offsets (c, 2, p)."""
    rng = np.random.default_rng([data_seed, 0])
    centers = rng.normal(size=(n_classes, p)) * sep
    offs = rng.normal(size=(n_classes, 2, p)) * within
    return centers, offs


def draw(centers, offs, n: int, noise: float, rng):
    """n labelled points of the mixture."""
    n_classes, p = centers.shape
    y = rng.integers(0, n_classes, size=n)
    sub = rng.integers(0, 2, size=n)
    x = centers[y] + offs[y, sub] + rng.normal(scale=noise, size=(n, p))
    return x.astype(np.float32), y.astype(np.int64)


def median_gamma(x: np.ndarray, seed: int, sample: int = 256) -> float:
    """gamma = 1 / median squared distance over a random row subsample."""
    x = np.asarray(x, np.float32)
    if x.shape[0] > sample:
        rows = np.random.default_rng(seed).choice(x.shape[0], sample,
                                                  replace=False)
        x = x[np.sort(rows)]
    d2 = ((x[:, None] - x[None]) ** 2).sum(-1)
    d2 = d2[d2 > 0]
    return float(1.0 / np.median(d2)) if d2.size else 1.0


def landmark_seed(cfg: dict) -> int:
    """The seed handed to `LPDSVM(seed=...)`, whose PRNG key takes 31 bits."""
    return int(cfg["data_seed"]) % (2 ** 31)


def make_job(cfg: dict, seed: int):
    """(x_train, y_train, x_test, y_test, gamma): the configuration's
    training set, and test rows drawn with ``seed``."""
    gen, ds = cfg["generator"], int(cfg["data_seed"])
    centers, offs = mixture(cfg["features"], cfg["classes"], gen["sep"],
                            gen["within"], ds)
    x, y = draw(centers, offs, cfg["train_rows"], gen["noise"],
                np.random.default_rng([ds, 1]))
    x_test, y_test = draw(centers, offs, cfg["test_rows"], gen["noise"],
                          np.random.default_rng([int(seed), 2]))
    return x, y, x_test, y_test, median_gamma(x, seed=ds)
