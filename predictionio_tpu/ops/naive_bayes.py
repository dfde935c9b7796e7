"""Multinomial Naive Bayes over dense nonnegative features.

Replaces Spark MLlib `NaiveBayes` as used by the classification template
(`examples/scala-parallel-classification/add-algorithm/src/main/scala/
NaiveBayesAlgorithm.scala:35-56`). MLlib's multinomial NB computes
per-class log priors pi_c = log(N_c / N) and log likelihoods theta_cj =
log((sum of feature j over class c + lambda) / (total over class c +
lambda * d)); prediction is argmax_c (pi_c + x . theta_c).

The whole fit is a couple of segment-sums and logs — one jit'd program.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclass
class NaiveBayesModel:
    pi: np.ndarray        # [n_classes] log priors
    theta: np.ndarray     # [n_classes, d] log likelihoods
    labels: np.ndarray    # [n_classes] original label values

    def sanity_check(self):
        assert np.isfinite(self.pi).all() and np.isfinite(self.theta).all()


@partial(jax.jit, static_argnames=("n_classes",))
def _fit(features, class_ix, valid, lam, *, n_classes: int):
    d = features.shape[1]
    features = features.astype(jnp.float32)   # narrow transfer widens here
    counts = jax.ops.segment_sum(valid.astype(jnp.float32), class_ix,
                                 num_segments=n_classes)
    feat_sums = jax.ops.segment_sum(features * valid[:, None], class_ix,
                                    num_segments=n_classes)
    pi = jnp.log(counts) - jnp.log(valid.sum())
    theta = (jnp.log(feat_sums + lam)
             - jnp.log(feat_sums.sum(axis=1, keepdims=True) + lam * d))
    return pi, theta


@jax.jit
def _scores(pi, theta, features):
    return pi[None, :] + features @ theta.T


def _integer_valued(a: np.ndarray) -> bool:
    """True iff every element is a whole number. Integer dtypes answer
    without touching the data; float inputs scan in row chunks so no
    features-sized temporary is ever allocated."""
    if np.issubdtype(a.dtype, np.integer) or a.dtype == bool:
        return True
    step = max(1, (1 << 22) // max(1, int(np.prod(a.shape[1:]))))
    for s in range(0, a.shape[0], step):
        chunk = a[s:s + step]
        if not np.equal(np.mod(chunk, 1.0), 0).all():
            return False
    return True


def nb_train(features: np.ndarray, labels: np.ndarray,
             lam: float = 1.0, *, mesh=None,
             timings: Optional[dict] = None) -> NaiveBayesModel:
    """features [n, d] nonnegative; labels [n] arbitrary floats/ints.

    `mesh` shards the sample dimension over the "data" axis: the fit is
    two segment-sums of sufficient statistics, so GSPMD turns the
    sharded inputs into per-device partial sums + an all-reduce (padding
    rows carry valid=0 and vanish from every statistic).

    The statistics are two segment-sums — compute is trivial next to
    moving [n, d] to the device (the split on a local chip: not
    measured) — so the feature upload narrows to the cheapest EXACT dtype:
    uint8 for integer counts < 256 (the multinomial regime — 1/4 the
    f32 bytes), uint16 below 65536, f32 otherwise; accumulation is f32
    in every case, so the statistics are bit-identical. `timings`, if
    given, is filled with transfer_s / solve_s wall-clock phases."""
    import time as _time

    if features.shape[0] == 0:
        raise ValueError("no training points")
    fmin = float(np.asarray(features).min(initial=0.0))
    if fmin < 0:
        raise ValueError("multinomial NB requires nonnegative features")
    uniq = np.unique(labels)
    class_ix = np.searchsorted(uniq, labels).astype(np.int32)
    src = np.asarray(features)
    feats_np = np.asarray(src, np.float32)   # zero-copy when already f32
    if 0 <= fmin and _integer_valued(src):
        fmax = feats_np.max(initial=0.0)
        if fmax < 256:
            feats_np = feats_np.astype(np.uint8)
        elif fmax < 65536:
            feats_np = feats_np.astype(np.uint16)
    t0 = _time.perf_counter()
    if mesh is not None:
        from predictionio_tpu.parallel import shard_put
        feats_d, _ = shard_put(feats_np, mesh)
        cix_d, _ = shard_put(class_ix, mesh)
        # mesh path: `valid` must share the padded sample sharding, so
        # it crosses with the rest of the transfer (n f32 bytes — small
        # next to the feature matrix) and is timed as transfer
        valid_d, _ = shard_put(np.ones(len(class_ix), np.float32), mesh)
    else:
        feats_d = jnp.asarray(feats_np)
        cix_d = jnp.asarray(class_ix)
        # single-device: `valid` is identically 1 — created on device,
        # nothing crosses the link
        valid_d = jnp.ones(len(class_ix), jnp.float32)
    if timings is not None:
        jax.block_until_ready((feats_d, cix_d, valid_d))
    t1 = _time.perf_counter()
    pi, theta = _fit(feats_d, cix_d, valid_d,
                     jnp.float32(lam), n_classes=len(uniq))
    out = NaiveBayesModel(np.asarray(pi), np.asarray(theta), uniq)
    if timings is not None:
        timings["transfer_s"] = t1 - t0
        timings["solve_s"] = _time.perf_counter() - t1
    return out


def nb_predict(model: NaiveBayesModel, features: np.ndarray) -> np.ndarray:
    """Returns predicted original label values, [b]."""
    scores = np.asarray(_scores(jnp.asarray(model.pi),
                                jnp.asarray(model.theta),
                                jnp.asarray(features, jnp.float32)))
    return model.labels[np.argmax(scores, axis=1)]


def nb_predict_proba(model: NaiveBayesModel,
                     features: np.ndarray) -> np.ndarray:
    scores = np.asarray(_scores(jnp.asarray(model.pi),
                                jnp.asarray(model.theta),
                                jnp.asarray(features, jnp.float32)))
    e = np.exp(scores - scores.max(axis=1, keepdims=True))
    return e / e.sum(axis=1, keepdims=True)
