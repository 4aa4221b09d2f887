"""A deployment's data from the seed: corpus rows, labels and attributes.

The generator is a copy of the repository's clustered one (Gaussian blobs
around unit-norm centres, the paper's stand-in for SIFT1M) with the paper's
labelling protocol (AIRSHIP arXiv:2210.14958 section 3): label = k-means
cluster id. The copy lives here so that the data, and the labels that the
reference checks constraints against, are made by the benchmark and not by
the system under test. Everything is made on the device in one jitted call.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _sqdist(a, b):
    """(A, d) x (B, d) squared L2 by the matmul expansion, f32 at HIGHEST."""
    a2 = jnp.sum(a * a, axis=-1, keepdims=True)
    b2 = jnp.sum(b * b, axis=-1, keepdims=True).T
    return jnp.maximum(a2 - 2.0 * jnp.matmul(a, b.T, precision=HIGHEST) + b2, 0.0)


@partial(jax.jit, static_argnames=(
    "n", "d", "n_labels", "spread", "attr_cols", "kmeans_sample", "kmeans_iters",
))
def _make(key, *, n, d, n_labels, spread, attr_cols, kmeans_sample, kmeans_iters):
    r_cent, r_assign, r_noise, r_fit, _, r_attr = jax.random.split(key, 6)
    centers = jax.random.normal(r_cent, (n_labels, d))
    centers = centers / jnp.linalg.norm(centers, axis=-1, keepdims=True)
    assign = jax.random.randint(r_assign, (n,), 0, n_labels, dtype=jnp.int32)
    vectors = centers[assign] + jax.random.normal(r_noise, (n, d)) * spread

    fit = vectors[jax.random.choice(r_fit, n, (min(kmeans_sample, n),), replace=False)]
    # Lloyd starts from the generating centres, so every seed's labels are
    # its blobs. From random rows it merged two blobs into one label on five
    # seeds of six and found all ten on the sixth, whose label walks were
    # half as long: the seed changed the work.
    cent = centers

    def lloyd(cent, _):
        one_hot = jax.nn.one_hot(jnp.argmin(_sqdist(fit, cent), axis=-1), n_labels)
        counts = jnp.sum(one_hot, axis=0)[:, None]
        new = jnp.matmul(one_hot.T, fit, precision=HIGHEST) / jnp.maximum(counts, 1.0)
        return jnp.where(counts > 0, new, cent), None

    cent, _ = jax.lax.scan(lloyd, cent, None, length=kmeans_iters)
    labels = jnp.argmin(_sqdist(vectors, cent), axis=-1).astype(jnp.int32)
    attrs = jax.random.uniform(r_attr, (n, attr_cols), jnp.float32)
    return vectors.astype(jnp.float32), labels, attrs


class Dataset:
    """Corpus on the device, with host copies of what the traffic and the
    checks read (labels, attributes) and a row gather for query picks."""

    def __init__(self, seed: int, cfg: dict):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), 0)
        self.vectors, labels, attrs = jax.block_until_ready(_make(
            key, n=cfg["n"], d=cfg["d"], n_labels=cfg["labels"],
            spread=cfg["spread"], attr_cols=cfg["attr_cols"],
            kmeans_sample=cfg["kmeans_sample"], kmeans_iters=cfg["kmeans_iters"],
        ))
        self.labels_dev, self.attrs_dev = labels, attrs
        self.labels = np.asarray(labels)
        self.attrs = np.asarray(attrs)
        self.n, self.d = self.vectors.shape
        self.n_labels = int(cfg["labels"])

    def rows(self, ids: np.ndarray) -> np.ndarray:
        return np.asarray(self.vectors[jnp.asarray(ids, jnp.int32)])
