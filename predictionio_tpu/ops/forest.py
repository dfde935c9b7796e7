"""Random-forest classifier, TPU-first.

Replaces MLlib's `RandomForest.trainClassifier` used by the reference's
classification template (`examples/scala-parallel-classification/
add-algorithm/src/main/scala/RandomForestAlgorithm.scala:41-72`).

MLlib grows trees by distributed recursive node splitting with per-node
candidate shuffles. The TPU formulation is **level-wise and dense** — the
whole forest advances one depth level per compiled step, with no
per-node control flow:

  1. Features are quantile-binned host-side into int32 bins `[n, f]`
     (the `maxBins` analog; split candidates = bin boundaries).
  2. All trees grow together. The class histogram
     `hist[tree, node, feature, bin, class]` for a level is built by one
     weight scatter-add keyed by (node*C + class, feature, bin) — the
     per-sample transients are the int32 key matrix `[n, f]`, the same
     size as the binned features themselves, so memory scales O(n*f)
     (a 1M x 100-feature train at 32 bins peaks well under 1 GB where a
     dense one-hot formulation would need 12.8 GB).
  3. Split selection is a vectorized argmax of impurity gain (gini or
     entropy) over `[f x B]` candidates per (tree, node), under a random
     per-node feature-subset mask (`featureSubsetStrategy`).
  4. Nodes whose best gain is <= 0 degrade to an always-left split, so
     every tree keeps the same static depth; leaves predict the majority
     class of their final histogram and the forest predicts by majority
     vote over trees.

Bagging matches MLlib: Poisson(1) bootstrap weights per (tree, sample)
when `n_trees > 1`, no bootstrap for a single tree.

Multi-chip: with a `mesh`, samples are block-sharded over the "data"
axis; each device scatter-adds a partial histogram from its local
samples and a [t, nd, f, B, C] `psum` over ICI reconstitutes the global
histogram (MLlib's per-node-group executor aggregation, as one
collective). Split selection is replicated (tiny), and sample routing to
child nodes stays local. Agreement with the single-device path is exact
and tested.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np



# rows sampled for quantile estimation: exact quantiles over millions
# of rows cost ~10x more host time for bin edges that differ in the
# third decimal (MLlib likewise samples its input for split finding,
# DecisionTree.findSplitsBins)
_QUANTILE_SAMPLE = 200_000


def quantile_bins(features: np.ndarray, max_bins: int,
                  seed: int = 0) -> np.ndarray:
    """Per-feature quantile bin edges `[f, max_bins - 1]` (host-side,
    once per training run; estimated from a row sample past
    `_QUANTILE_SAMPLE` rows)."""
    n = features.shape[0]
    if n > _QUANTILE_SAMPLE:
        ix = np.random.RandomState(seed).choice(
            n, _QUANTILE_SAMPLE, replace=False)
        features = features[ix]
    qs = np.linspace(0, 1, max_bins + 1)[1:-1]
    return np.quantile(features, qs, axis=0).T.astype(np.float32)


def apply_bins(features: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """Bin features `[n, f]` into [0, B), in the smallest integer dtype
    that holds the bins (uint8 below 256 bins — also the transfer-lean
    form — else int32). Works on a transposed copy so every searchsorted
    reads a contiguous column (measured ~1.4x on the 1Mx100 bench
    host)."""
    xt = np.ascontiguousarray(np.asarray(features, np.float32).T)
    f, n = xt.shape
    out = np.empty((f, n), np.uint8 if edges.shape[1] < 256 else np.int32)
    for j in range(f):
        out[j] = np.searchsorted(edges[j], xt[j], side="right")
    return np.ascontiguousarray(out.T)


def _subset_size(strategy: str, n_features: int, n_trees: int) -> int:
    """featureSubsetStrategy -> features considered per node (MLlib
    semantics: 'auto' = all for one tree, sqrt for a forest)."""
    if strategy == "auto":
        strategy = "all" if n_trees == 1 else "sqrt"
    if strategy == "all":
        return n_features
    if strategy == "sqrt":
        return max(1, int(math.sqrt(n_features)))
    if strategy == "log2":
        return max(1, int(math.log2(n_features)))
    if strategy == "onethird":
        return max(1, n_features // 3)
    raise ValueError(f"Unknown featureSubsetStrategy {strategy!r}")


def _impurity(counts, total, kind: str):
    """counts [..., C], total [..., 1] -> impurity [...]."""
    p = counts / jnp.maximum(total, 1e-9)
    if kind == "gini":
        return 1.0 - (p * p).sum(-1)
    if kind == "entropy":
        return -(p * jnp.where(p > 0, jnp.log2(jnp.maximum(p, 1e-12)),
                               0.0)).sum(-1)
    raise ValueError(f"Unknown impurity {kind!r}")


# transient budget for the histogram scatter keys: the [t, chunk, f]
# int32 key block (and its weight broadcast) stays under this many bytes,
# so a 1M x 100 x 10-tree level never materializes the full [t, n*f]
# index space (which OOMs at ~6 GB x 3 temps on a 16 GiB chip)
_HIST_KEY_BUDGET = 256 << 20


def _histogram(s, w, fb_cols, *, n_nodes: int, c: int, f: int, b: int):
    """Partial class histogram from (this device's) samples.

    s:       [t, n]  node*C + class per (tree, sample)
    w:       [t, n]  bootstrap weights
    fb_cols: [n, f]  flat feature-bin column f*B + bin
    Returns [t, nd, f, B, C]. Scatter-adds keyed by (s, feature-bin) —
    never a dense one-hot. Large sample counts are processed in
    lax.scan chunks so the [t, chunk, f] key transients respect
    `_HIST_KEY_BUDGET`.
    """
    t, n = s.shape
    size = n_nodes * c * f * b

    def add_block(hist, s_blk, w_blk, fb_blk):
        def one_tree(h_t, s_t, w_t):
            keys = s_t[:, None] * (f * b) + fb_blk       # [chunk, f]
            upd = jnp.broadcast_to(w_t[:, None], keys.shape)
            return h_t.at[keys.reshape(-1)].add(upd.reshape(-1))

        return jax.vmap(one_tree)(hist, s_blk, w_blk)

    chunk = max(1, _HIST_KEY_BUDGET // (max(t, 1) * max(f, 1) * 4))
    if chunk >= n:
        hist = add_block(jnp.zeros((t, size), jnp.float32), s, w, fb_cols)
    else:
        n_chunks = -(-n // chunk)
        npad = n_chunks * chunk
        # pad with weight-0 samples keyed to slot 0 (invisible)
        s_p = jnp.pad(s, ((0, 0), (0, npad - n)))
        w_p = jnp.pad(w, ((0, 0), (0, npad - n)))
        fb_p = jnp.pad(fb_cols, ((0, npad - n), (0, 0)))
        xs = (s_p.reshape(t, n_chunks, chunk).transpose(1, 0, 2),
              w_p.reshape(t, n_chunks, chunk).transpose(1, 0, 2),
              fb_p.reshape(n_chunks, chunk, f))

        def body(hist, blk):
            return add_block(hist, *blk), None

        hist, _ = jax.lax.scan(body, jnp.zeros((t, size), jnp.float32), xs)
    return hist.reshape(t, n_nodes, c, f, b).transpose(0, 1, 3, 4, 2)


def _select_splits(key, hist, *, n_nodes: int, c: int, f: int, b: int,
                   subset: int, impurity: str):
    """Vectorized split selection from the GLOBAL histogram
    [t, nd, f, B, C]; pure replicated math."""
    t = hist.shape[0]
    # threshold "<= bin" -> left counts = cumsum over B
    left = jnp.cumsum(hist, axis=3)
    total = left[:, :, :, -1, :]                   # [t, nd, f, C]
    right = total[:, :, :, None, :] - left
    nl = left.sum(-1)                              # [t, nd, f, B]
    nr = right.sum(-1)
    nt = nl + nr
    imp_l = _impurity(left, nl[..., None], impurity)
    imp_r = _impurity(right, nr[..., None], impurity)
    parent = total[:, :, 0, :]                     # [t, nd, C]
    n_parent = parent.sum(-1)                      # [t, nd]
    imp_p = _impurity(parent, n_parent[..., None], impurity)
    child = (nl * imp_l + nr * imp_r) / jnp.maximum(nt, 1e-9)
    gain = imp_p[:, :, None, None] - child         # [t, nd, f, B]

    # the last bin is "everything left" = no split; forbid it as a
    # candidate, and forbid features outside the random subset
    gain = gain.at[:, :, :, -1].set(-jnp.inf)
    ranks = jnp.argsort(
        jax.random.uniform(key, (t, n_nodes, f)), axis=-1).argsort(-1)
    gain = jnp.where((ranks < subset)[:, :, :, None], gain, -jnp.inf)

    flat = gain.reshape(t, n_nodes, f * b)
    best = jnp.argmax(flat, axis=-1)               # [t, nd]
    best_gain = jnp.take_along_axis(flat, best[..., None], -1)[..., 0]
    split_f = best // b
    split_b = best % b
    # non-positive gain (or empty node) -> always-left split
    degenerate = ~(best_gain > 0)
    split_f = jnp.where(degenerate, 0, split_f).astype(jnp.int32)
    split_b = jnp.where(degenerate, b - 1, split_b).astype(jnp.int32)
    return split_f, split_b


def _route(xb, node, split_f, split_b):
    """Move each (tree, sample) to its child node; purely local."""
    t = node.shape[0]
    feat_vals = xb[jnp.arange(xb.shape[0])[None, :], split_f[
        jnp.arange(t)[:, None], node]]             # [t, n]
    go_right = feat_vals > split_b[jnp.arange(t)[:, None], node]
    return node * 2 + go_right.astype(jnp.int32)


@partial(jax.jit, static_argnames=("n_nodes", "n_classes", "n_features",
                                   "n_bins", "subset", "impurity", "mesh"))
def _grow_level(key, fb_cols, node, y, w, xb, *, n_nodes: int,
                n_classes: int, n_features: int, n_bins: int, subset: int,
                impurity: str, mesh=None):
    """One level for every tree at once.

    fb_cols: [n, f]   flat feature-bin columns (shared across trees)
    node:    [t, n]   current node of each sample in each tree
    y:       [n]      class ids
    w:       [t, n]   bootstrap weights
    xb:      [n, f]   binned features
    Returns (split_feature [t, nd], split_bin [t, nd], new node [t, n]).
    With a mesh, the sample dimension is sharded over "data": per-device
    partial histograms + one psum, replicated split selection, local
    routing.
    """
    f, b, c = n_features, n_bins, n_classes
    kw = dict(n_nodes=n_nodes, c=c, f=f, b=b)

    def level(key, fb_cols, node, y, w, xb, *, hist_reduce):
        s = node * c + y[None, :]
        hist = hist_reduce(_histogram(s, w, fb_cols, **kw))
        split_f, split_b = _select_splits(
            key, hist, subset=subset, impurity=impurity, **kw)
        return split_f, split_b, _route(xb, node, split_f, split_b)

    if mesh is None:
        return level(key, fb_cols, node, y, w, xb, hist_reduce=lambda h: h)

    from jax.sharding import PartitionSpec as P

    body = partial(level,
                   hist_reduce=lambda h: jax.lax.psum(h, "data"))
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P("data", None), P(None, "data"), P("data"),
                  P(None, "data"), P("data", None)),
        out_specs=(P(), P(), P(None, "data")))(
            key, fb_cols, node, y, w, xb)


@partial(jax.jit, static_argnames=("n_nodes", "n_classes", "mesh"))
def _leaf_counts(node, y, w, *, n_nodes: int, n_classes: int, mesh=None):
    def counts(node, y, w, *, reduce):
        s = node * n_classes + y[None, :]

        def one_tree(s_t, w_t):
            return jnp.zeros((n_nodes * n_classes,),
                             jnp.float32).at[s_t].add(w_t)

        return reduce(jax.vmap(one_tree)(s, w)).reshape(
            -1, n_nodes, n_classes)

    if mesh is None:
        return counts(node, y, w, reduce=lambda x: x)

    from jax.sharding import PartitionSpec as P

    body = partial(counts, reduce=lambda x: jax.lax.psum(x, "data"))
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, "data"), P("data"), P(None, "data")),
        out_specs=P())(node, y, w)


@dataclass
class ForestModel:
    """Level-order flattened forest: internal node i at level l sits at
    global index 2^l - 1 + i."""
    bin_edges: np.ndarray       # [f, B-1]
    split_feature: np.ndarray   # [t, 2^depth - 1]
    split_bin: np.ndarray       # [t, 2^depth - 1]
    leaf_class: np.ndarray      # [t, 2^depth]
    classes: np.ndarray         # [C] original label values
    max_depth: int

    @property
    def n_trees(self) -> int:
        return self.split_feature.shape[0]

    def sanity_check(self):
        assert self.split_feature.shape == self.split_bin.shape
        assert self.leaf_class.shape[1] == 2 ** self.max_depth

    # below this many (tree, sample) traversals, host numpy wins (device
    # dispatch overhead dominates single-query serving); above it, the
    # jit'd traversal keeps eval sweeps / batchpredict on the device
    HOST_CROSSOVER_CELLS = 1 << 14

    def predict(self, features: np.ndarray) -> np.ndarray:
        """Majority vote over trees; returns original label values.
        Size-dispatched: big batches run the jit'd device traversal, tiny
        ones the equivalent host loop. Tie-breaking (lowest class index)
        is identical on both paths."""
        xb = apply_bins(np.asarray(features, np.float32), self.bin_edges)
        t, n = self.n_trees, xb.shape[0]
        c = len(self.classes)
        if t * n >= self.HOST_CROSSOVER_CELLS:
            ix = np.asarray(_predict_device(
                jnp.asarray(xb), jnp.asarray(self.split_feature),
                jnp.asarray(self.split_bin), jnp.asarray(self.leaf_class),
                max_depth=self.max_depth, n_classes=c))
            return self.classes[ix]
        node = np.zeros((t, n), np.int32)
        rows = np.arange(n)[None, :]
        trees = np.arange(t)[:, None]
        for level in range(self.max_depth):
            off = (1 << level) - 1
            sf = self.split_feature[trees, off + node]
            sb = self.split_bin[trees, off + node]
            node = node * 2 + (xb[rows, sf] > sb)
        votes = self.leaf_class[trees, node]             # [t, n]
        # per-sample class counts in one bincount: flat id = class*n + col
        counts = np.bincount(
            (votes.astype(np.int64) * n + np.arange(n)).ravel(),
            minlength=c * n).reshape(c, n)
        return self.classes[np.argmax(counts, axis=0)]


@partial(jax.jit, static_argnames=("max_depth", "n_classes"))
def _predict_device(xb, split_feature, split_bin, leaf_class, *,
                    max_depth: int, n_classes: int):
    """Device forest traversal: level-unrolled gathers + one-hot vote
    count; returns class indices [n] (argmax ties -> lowest index, the
    host path's np.argmax convention)."""
    t, n = split_feature.shape[0], xb.shape[0]
    node = jnp.zeros((t, n), jnp.int32)
    rows = jnp.arange(n)[None, :]
    trees = jnp.arange(t)[:, None]
    for level in range(max_depth):
        off = (1 << level) - 1
        sf = split_feature[trees, off + node]
        sb = split_bin[trees, off + node]
        node = node * 2 + (xb[rows, sf] > sb).astype(jnp.int32)
    votes = leaf_class[trees, node]                      # [t, n]
    counts = jax.nn.one_hot(votes, n_classes, dtype=jnp.float32).sum(0)
    return jnp.argmax(counts, axis=1)


def forest_train(features: np.ndarray, labels: np.ndarray, *,
                 n_trees: int = 10, max_depth: int = 5, max_bins: int = 32,
                 impurity: str = "gini",
                 feature_subset_strategy: str = "auto",
                 seed: int = 0, mesh=None,
                 timings: dict = None) -> ForestModel:
    """Train a random forest on dense features [n, f] and labels [n].
    `mesh` shards the sample dimension over the "data" axis (partial
    histograms + psum); None runs single-device. `timings`, if given,
    is filled with bin_s (host quantile binning) and device_s (upload +
    level loop + fetch) wall-clock phases."""
    import time as _time

    t0 = _time.perf_counter()
    features = np.asarray(features, np.float32)
    labels = np.asarray(labels)
    classes, y_np = np.unique(labels, return_inverse=True)
    n, f = features.shape
    c = max(len(classes), 2)
    edges = quantile_bins(features, max_bins)
    xb_np = apply_bins(features, edges)
    subset = _subset_size(feature_subset_strategy, f, n_trees)
    t_bin = _time.perf_counter()

    key = jax.random.PRNGKey(seed)
    kboot, key = jax.random.split(key)
    if n_trees == 1:
        w = jnp.ones((1, n), jnp.float32)
    else:
        w = jax.random.poisson(kboot, 1.0, (n_trees, n)).astype(jnp.float32)

    # binned features cross the host->device link at uint8 (max_bins is
    # bounded at 256) and widen device-side; fb_cols is DERIVED on
    # device — together this cuts the 1Mx100 upload from 720 MB of int32
    # to 90 MB (what that saves on a local chip: not measured)
    xb_small = (np.asarray(xb_np, np.uint8) if max_bins <= 256
                else np.asarray(xb_np, np.int32))
    y_np32 = y_np.astype(np.int32)
    if mesh is not None:
        # pad samples to a device multiple with weight-0 rows (invisible
        # to every histogram) and shard the sample dimension
        from predictionio_tpu.parallel import pad_rows, pad_to_multiple

        n_dev = int(mesh.shape["data"])
        npad = pad_to_multiple(max(n, n_dev), n_dev)
        xb_small = pad_rows(xb_small, npad)
        y_np32 = pad_rows(y_np32, npad)
        w = jnp.pad(w, ((0, 0), (0, npad - n)))
        n = npad
    xb = jnp.asarray(xb_small).astype(jnp.int32)
    fb_cols = xb + jnp.arange(f, dtype=jnp.int32)[None, :] * max_bins
    y = jnp.asarray(y_np32)
    node = jnp.zeros((n_trees, n), jnp.int32)

    split_fs, split_bs = [], []
    for level in range(max_depth):
        key, klevel = jax.random.split(key)
        sf, sb, node = _grow_level(
            klevel, fb_cols, node, y, w, xb, n_nodes=1 << level,
            n_classes=c, n_features=f, n_bins=max_bins, subset=subset,
            impurity=impurity, mesh=mesh)
        # keep sf/sb on device: fetching per level costs a host round
        # trip each; one batched fetch below covers all levels
        split_fs.append(sf)
        split_bs.append(sb)

    counts = _leaf_counts(node, y, w, n_nodes=1 << max_depth, n_classes=c,
                          mesh=mesh)
    split_fs = [np.asarray(a) for a in jax.device_get(split_fs)]
    split_bs = [np.asarray(a) for a in jax.device_get(split_bs)]
    # empty leaves (never reached in training) fall back to the global
    # class distribution — computed from the ORIGINAL labels (the mesh
    # path pads y with class-0 rows, which must not skew the fallback)
    global_counts = jnp.asarray(
        np.bincount(y_np, minlength=c).astype(np.float32))
    counts = counts + 1e-6 * global_counts[None, None, :]
    leaf_class = np.asarray(jnp.argmax(counts, axis=-1), np.int32)
    if timings is not None:
        timings["bin_s"] = t_bin - t0
        timings["device_s"] = _time.perf_counter() - t_bin

    return ForestModel(
        bin_edges=edges,
        split_feature=np.concatenate(split_fs, axis=1),
        split_bin=np.concatenate(split_bs, axis=1),
        leaf_class=leaf_class,
        classes=classes.astype(np.float32),
        max_depth=max_depth)
