"""Sequential recommendation: a causal transformer over item histories.

A NEW capability beyond the reference, like `ops/twotower.py`
(SURVEY.md §7 phase 7): the reference's recommenders are order-blind
(ALS factorizes a rating matrix, `examples/scala-parallel-recommendation`),
while this model predicts the NEXT item from the ORDER of a user's
events — the SASRec-style architecture (Kang & McAuley 2018,
reimplemented from the paper's description) that ALS deployments
graduate to, and the framework's long-context/sequence-parallel proof
point.

TPU design:
  - ONE jit'd train step over pre-uploaded batches via `lax.scan`
    (one dispatch per epoch instead of one per step; the gain on a
    local chip is not measured).
  - attention runs through `ops.attention.ring_attention`: the sequence
    dimension shards over the mesh "sp" axis and K/V circulate over ICI
    `ppermute`, so context length scales with the ring — the batch
    dimension shards over "data" with gradient psums, both expressed as
    shardings on ONE jit (GSPMD inserts the collectives).
  - the item embedding table is TIED between input encoding and the
    output softmax (halves the parameter bytes that cross the link).
  - in-batch sampled softmax against the batch's target items (the
    two-tower recipe) — no [B, n_items] logits materialize in training.

Serving encodes the user's RECENT history read from the event store at
query time (the e-commerce template's serve-time-read pattern,
ECommAlgorithm.scala:331-430) and scores all items with one masked
top-k matmul (`ops.topk`).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.ops.attention import ring_attention


@dataclass
class SeqRecModel:
    params: dict           # transformer weights (numpy pytree)
    seq_len: int
    n_items: int
    n_heads: int

    @property
    def item_emb(self) -> np.ndarray:
        """[n_items, D] tied output/input item table (PAD row dropped)."""
        return np.asarray(self.params["item_table"])[:self.n_items]

    def sanity_check(self):
        assert all(np.isfinite(v).all() for v in
                   jax.tree_util.tree_leaves(self.params))

    def __getstate__(self):
        # the serve-time device-param cache (_devp) must not be pickled
        # with the model (persistence stores numpy weights only)
        d = dict(self.__dict__)
        d.pop("_devp", None)
        return d


def _init_params(key, n_items: int, seq_len: int, dim: int,
                 n_layers: int):
    ks = iter(jax.random.split(key, 4 + 7 * n_layers))

    def dense(fan_in, fan_out):
        return (jax.random.normal(next(ks), (fan_in, fan_out),
                                  jnp.float32) / np.sqrt(fan_in))

    p = {
        # row n_items is the PAD embedding (kept at its random init;
        # attention masks PAD keys so it never leaks into real rows)
        "item_table": jax.random.normal(
            next(ks), (n_items + 1, dim), jnp.float32) / np.sqrt(dim),
        "pos_emb": jax.random.normal(
            next(ks), (seq_len, dim), jnp.float32) * 0.02,
        "ln_f": jnp.ones(dim), "ln_f_b": jnp.zeros(dim),
    }
    for layer in range(n_layers):
        p[f"l{layer}"] = {
            "ln1": jnp.ones(dim), "ln1_b": jnp.zeros(dim),
            "wq": dense(dim, dim), "wk": dense(dim, dim),
            "wv": dense(dim, dim), "wo": dense(dim, dim),
            "ln2": jnp.ones(dim), "ln2_b": jnp.zeros(dim),
            "w1": dense(dim, 2 * dim), "w2": dense(2 * dim, dim),
        }
    return p


def _ln(x, g, b):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-6) * g + b


def _encode(params, seqs, *, n_items: int, n_heads: int, n_layers: int,
            mesh=None):
    """seqs [B, S] int32 (PAD = n_items, right-aligned) -> [B, D] the
    final-position representation."""
    B, S = seqs.shape
    D = params["pos_emb"].shape[1]
    Dh = D // n_heads
    valid = seqs != n_items                                # [B, S]
    x = params["item_table"][seqs] * np.sqrt(D) + params["pos_emb"]

    # ring_attention's trivial-axis fall-through handles mesh=None too
    attend = partial(ring_attention, mesh=mesh)
    for layer in range(n_layers):
        lp = params[f"l{layer}"]
        h = _ln(x, lp["ln1"], lp["ln1_b"])
        q = (h @ lp["wq"]).reshape(B, S, n_heads, Dh)
        k = (h @ lp["wk"]).reshape(B, S, n_heads, Dh)
        v = (h @ lp["wv"]).reshape(B, S, n_heads, Dh)
        a = attend(q, k, v, causal=True, kv_mask=valid)
        x = x + a.reshape(B, S, D) @ lp["wo"]
        h = _ln(x, lp["ln2"], lp["ln2_b"])
        x = x + jax.nn.relu(h @ lp["w1"]) @ lp["w2"]
    x = _ln(x, params["ln_f"], params["ln_f_b"])
    return x[:, -1, :]                     # right-aligned: last = newest


def _loss_fn(params, seqs, targets, temperature, *, n_items, n_heads,
             n_layers, mesh):
    u = _encode(params, seqs, n_items=n_items, n_heads=n_heads,
                n_layers=n_layers, mesh=mesh)
    t = params["item_table"][targets]                      # [B, D]
    logits = (u @ t.T) / temperature                       # in-batch
    labels = jnp.arange(seqs.shape[0])
    return -jnp.mean(jax.nn.log_softmax(logits)[labels, labels])


def seqrec_train(sequences: np.ndarray, targets: np.ndarray, *,
                 n_items: int, seq_len: int, dim: int = 64,
                 n_heads: int = 2, n_layers: int = 2,
                 batch_size: int = 256, epochs: int = 5,
                 lr: float = 3e-3, temperature: float = 0.07,
                 seed: int = 0, mesh=None,
                 init_params=None) -> SeqRecModel:
    """Train on [N, seq_len] right-aligned item-id sequences (PAD =
    n_items) with [N] next-item targets. `mesh` shards the batch over
    "data" and — when the mesh has an "sp" axis — the sequence over it
    via ring attention. `init_params` resumes from a prior model's
    weights (the streaming warm-start mini-epoch); optimizer state
    starts fresh."""
    import optax

    assert sequences.shape[1] == seq_len
    if init_params is not None:
        params = jax.tree_util.tree_map(jnp.asarray, init_params)
    else:
        params = _init_params(jax.random.PRNGKey(seed), n_items,
                              seq_len, dim, n_layers)
    opt = optax.adam(lr)
    opt_state = opt.init(params)
    n = (len(sequences) // batch_size) * batch_size
    if n == 0:
        raise ValueError(
            f"need at least one full batch ({batch_size}) of sequences")
    seq_all = jnp.asarray(sequences[:n].reshape(-1, batch_size, seq_len)
                          .astype(np.int32))
    tgt_all = jnp.asarray(targets[:n].reshape(-1, batch_size)
                          .astype(np.int32))

    loss = partial(_loss_fn, temperature=jnp.float32(temperature),
                   n_items=n_items, n_heads=n_heads, n_layers=n_layers,
                   mesh=mesh)

    @jax.jit
    def epoch(params, opt_state, seq_all, tgt_all):
        def body(carry, batch):
            params, opt_state = carry
            seqs, tgts = batch
            g = jax.grad(loss)(params, seqs, tgts)
            updates, opt_state = opt.update(g, opt_state, params)
            return (optax.apply_updates(params, updates),
                    opt_state), None

        (params, opt_state), _ = jax.lax.scan(
            body, (params, opt_state), (seq_all, tgt_all))
        return params, opt_state

    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        seq_all = jax.device_put(
            seq_all, NamedSharding(mesh, P(None, "data", None)))
        tgt_all = jax.device_put(
            tgt_all, NamedSharding(mesh, P(None, "data")))
    for _ in range(epochs):
        params, opt_state = epoch(params, opt_state, seq_all, tgt_all)
    params_np = jax.tree_util.tree_map(np.asarray, params)
    return SeqRecModel(params=params_np, seq_len=seq_len,
                       n_items=n_items, n_heads=n_heads)


@partial(jax.jit, static_argnames=("n_items", "n_heads", "n_layers"))
def _encode_jit(params, seqs, *, n_items, n_heads, n_layers):
    return _encode(params, seqs, n_items=n_items, n_heads=n_heads,
                   n_layers=n_layers, mesh=None)


def seqrec_encode(model: SeqRecModel, seqs: np.ndarray) -> np.ndarray:
    """[B, seq_len] histories -> [B, D] user representations. The
    SERVING hot path: device-resident params are cached on the model
    (outside its pickled state, see SeqRecModel.__getstate__) and the
    encoder runs as one jitted program instead of eager per-op
    dispatch."""
    devp = getattr(model, "_devp", None)
    if devp is None:
        devp = jax.tree_util.tree_map(jnp.asarray, model.params)
        model._devp = devp
    n_layers = sum(1 for k in model.params if k.startswith("l")
                   and k[1:].isdigit())
    out = _encode_jit(devp, jnp.asarray(seqs.astype(np.int32)),
                      n_items=model.n_items, n_heads=model.n_heads,
                      n_layers=n_layers)
    return np.asarray(out)


def build_sequences(user_ix: np.ndarray, item_ix: np.ndarray,
                    t_millis: np.ndarray, *, n_items: int, seq_len: int,
                    min_len: int = 2):
    """Group events into per-user time-ordered item sequences and emit
    (sequences [N, seq_len] right-aligned PAD=n_items, targets [N]):
    for each user with >= min_len events, the history-before-last is
    the sequence and the last item the target. Host-side, vectorized
    (no per-user Python loop)."""
    order = np.lexsort((t_millis, user_ix))
    u, i = user_ix[order], item_ix[order]
    starts = np.r_[0, np.flatnonzero(np.diff(u)) + 1]
    ends = np.r_[starts[1:], len(u)]
    lens = ends - starts
    keep = lens >= min_len
    starts, ends, lens = starts[keep], ends[keep], lens[keep]
    n = len(starts)
    seqs = np.full((n, seq_len), n_items, np.int32)
    # history = up to seq_len items BEFORE the last; right-aligned
    hist_len = np.minimum(lens - 1, seq_len)
    # flat gather: for row r, take items [end-1-hist .. end-1)
    rows = np.repeat(np.arange(n), hist_len)
    offs = (np.arange(int(hist_len.sum()))
            - np.repeat(np.cumsum(hist_len) - hist_len, hist_len))
    src = np.repeat(ends - 1 - hist_len, hist_len) + offs
    cols = np.repeat(seq_len - hist_len, hist_len) + offs
    seqs[rows, cols] = i[src]
    targets = i[ends - 1].astype(np.int32)
    return seqs, targets
