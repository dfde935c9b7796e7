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

The layer stack is data (`ops/backbone.py`): this module's own small
block is the configuration `sasrec`, and a public architecture's stack
at its published widths is another configuration of the same code.

Serving reads the user's history from the event store at query time
(the e-commerce template's serve-time-read pattern,
ECommAlgorithm.scala:331-430), PACKS the batch's histories on one token
axis (`PackedEncoder`: a few token buckets compiled ahead of time, so
no query compiles), and hands each history's last position to the
catalog top-k serve plan (`ops.topk`).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, replace
from functools import partial
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.obs import trace
from predictionio_tpu.ops import backbone as bb
from predictionio_tpu.ops import moe, ssm
from predictionio_tpu.ops.attention import packed_attention, ring_attention


@dataclass
class SeqRecModel:
    params: dict           # the stack's weights, one pytree keyed by layer
    n_items: int
    backbone: Dict[str, Any]    # `bb.config_dict` of the stack's config

    @functools.cached_property
    def config(self) -> bb.BackboneConfig:
        return bb.config_of(self.backbone)

    @property
    def seq_len(self) -> int:
        return self.config.max_history

    @functools.cached_property
    def item_emb(self) -> np.ndarray:
        """[n_items, D] float32, the table the last position is scored
        against (the item table itself where it is tied; PAD row
        dropped)."""
        return np.asarray(bb.head_table(self.params, self.config),
                          np.float32)[:self.n_items]

    def sanity_check(self):
        assert all(np.isfinite(np.asarray(v, np.float32)).all() for v in
                   jax.tree_util.tree_leaves(self.params))

    def __getstate__(self):
        # the serve-time device-param cache (_devp) must not be pickled
        # with the model (persistence stores numpy weights only)
        d = dict(self.__dict__)
        for cached in ("_devp", "config", "item_emb"):
            d.pop(cached, None)
        return d


def resolve_backbone(backbone: str, *, dim: int, n_heads: int,
                     n_layers: int, seq_len: int,
                     n_items: int) -> bb.BackboneConfig:
    """`sasrec` is built from the template's own parameters; anything
    else is a configuration file, a stack whose vocabulary is this
    catalog."""
    if backbone == "sasrec":
        return bb.sasrec_config(dim=dim, n_heads=n_heads,
                                n_layers=n_layers, seq_len=seq_len,
                                n_items=n_items)
    return replace(bb.load_config(backbone), vocab=n_items)


def _encode(params, seqs, *, cfg: bb.BackboneConfig, n_items: int,
            mesh=None):
    """seqs [B, S] int32 (PAD = n_items, right-aligned) -> [B, D] the
    final-position representation."""
    valid = seqs != n_items                                # [B, S]
    if cfg.positions:           # learned: a slot of the padded window
        pos = jnp.broadcast_to(jnp.arange(seqs.shape[1]), seqs.shape)
    else:                       # the event's index in its own history
        pos = jnp.maximum(jnp.cumsum(valid, axis=1) - 1, 0)

    # ring_attention's trivial-axis fall-through handles mesh=None too
    def attend(q, k, v, *, window, sink):
        return ring_attention(q, k, v, mesh, causal=True, kv_mask=valid,
                              window=window, sink=sink)

    x, _ = bb.forward(params, cfg, seqs, pos, attend, valid=valid)
    return x[:, -1, :]                     # right-aligned: last = newest


def _loss_fn(params, seqs, targets, temperature, *, cfg, n_items, mesh):
    u = _encode(params, seqs, cfg=cfg, n_items=n_items, mesh=mesh)
    t = bb.head_table(params, cfg)[targets].astype(jnp.float32)  # [B, D]
    logits = (u @ t.T) / temperature                       # in-batch
    labels = jnp.arange(seqs.shape[0])
    return -jnp.mean(jax.nn.log_softmax(logits)[labels, labels])


def seqrec_train(sequences: np.ndarray, targets: np.ndarray, *,
                 n_items: int, seq_len: int, dim: int = 64,
                 n_heads: int = 2, n_layers: int = 2,
                 batch_size: int = 256, epochs: int = 5,
                 lr: float = 3e-3, temperature: float = 0.07,
                 seed: int = 0, mesh=None,
                 init_params=None, backbone: str = "sasrec",
                 losses: Optional[List[float]] = None) -> SeqRecModel:
    """Train on [N, seq_len] right-aligned item-id sequences (PAD =
    n_items) with [N] next-item targets. `mesh` shards the batch over
    "data" and — when the mesh has an "sp" axis — the sequence over it
    via ring attention. `init_params` resumes from a prior model's
    weights (the streaming warm-start mini-epoch); optimizer state
    starts fresh. `backbone` is the stack (`sasrec`: built from dim /
    n_heads / n_layers; else a configuration file). A router's correction bias is held
    constant (its balancing update is no gradient step and is not
    made here). `losses` collects every step's loss."""
    import optax

    assert sequences.shape[1] == seq_len
    cfg = resolve_backbone(backbone, dim=dim, n_heads=n_heads,
                           n_layers=n_layers, seq_len=seq_len,
                           n_items=n_items)
    if init_params is not None:
        params = jax.tree_util.tree_map(jnp.asarray, init_params)
    else:
        params = bb.init_params(jax.random.PRNGKey(seed), cfg)
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
                   cfg=cfg, n_items=n_items, mesh=mesh)

    @jax.jit
    def epoch(params, opt_state, seq_all, tgt_all):
        def body(carry, batch):
            params, opt_state = carry
            seqs, tgts = batch
            value, g = jax.value_and_grad(loss)(params, seqs, tgts)
            for layer in range(len(cfg.layers)):
                ffn = g[f"l{layer}"].get("ffn", {})
                if "bias" in ffn:       # the correction bias is no weight
                    ffn["bias"] = jnp.zeros_like(ffn["bias"])
            updates, opt_state = opt.update(g, opt_state, params)
            return (optax.apply_updates(params, updates),
                    opt_state), value

        return jax.lax.scan(body, (params, opt_state),
                            (seq_all, tgt_all))

    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        seq_all = jax.device_put(
            seq_all, NamedSharding(mesh, P(None, "data", None)))
        tgt_all = jax.device_put(
            tgt_all, NamedSharding(mesh, P(None, "data")))
    for _ in range(epochs):
        (params, opt_state), values = epoch(params, opt_state, seq_all,
                                            tgt_all)
        if losses is not None:
            losses.extend(float(v) for v in np.asarray(values))
    params_np = jax.tree_util.tree_map(np.asarray, params)
    return SeqRecModel(params=params_np, n_items=n_items,
                       backbone=bb.config_dict(cfg))


@partial(jax.jit, static_argnames=("cfg", "n_items"))
def _encode_jit(params, seqs, *, cfg, n_items):
    return _encode(params, seqs, cfg=cfg, n_items=n_items, mesh=None)


def device_params(model: SeqRecModel):
    """The model's weights on the device, cached on the model outside
    its pickled state (see SeqRecModel.__getstate__)."""
    devp = getattr(model, "_devp", None)
    if devp is None:
        devp = jax.tree_util.tree_map(jnp.asarray, model.params)
        model._devp = devp
    return devp


def seqrec_encode(model: SeqRecModel, seqs: np.ndarray) -> np.ndarray:
    """[B, seq_len] padded histories -> [B, D] user representations:
    the offline form (evaluation, tests), one jitted program a shape.
    The serve path packs instead (`PackedEncoder`)."""
    out = _encode_jit(device_params(model),
                      jnp.asarray(seqs.astype(np.int32)),
                      cfg=model.config, n_items=model.n_items)
    return np.asarray(out)


# -- the serve path: packed histories in token buckets ------------------------

_SEQ_METRICS = None


def _seq_metrics():
    """The packed encoder's counters, in the process-default registry
    (lazy, like `ops/topk._dispatch_total`)."""
    global _SEQ_METRICS
    if _SEQ_METRICS is None:
        from predictionio_tpu.obs import get_registry
        reg = get_registry()
        share = (0.01, 0.02, 0.05, 0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7,
                 0.8, 0.9, 1.0)
        _SEQ_METRICS = {
            "tokens": reg.histogram(
                "pio_seq_call_tokens",
                "Live tokens (history events) of one call of the "
                "packed stack",
                buckets=tuple(float(2 ** i) for i in range(4, 15))),
            "pad": reg.histogram(
                "pio_seq_pad_share",
                "Padding tokens over the token bucket, per call of the "
                "packed stack", buckets=share),
            "load": reg.histogram(
                "pio_moe_expert_tokens_max_over_mean",
                "Tokens of the busiest held expert over the held "
                "experts' mean, mean over the expert layers, per call",
                buckets=(1.0, 1.1, 1.2, 1.35, 1.5, 1.75, 2.0, 2.5, 3.0,
                         4.0, 6.0, 8.0, 16.0)),
            "unrouted": reg.histogram(
                "pio_moe_unrouted_share",
                "Share of a call's live tokens none of whose experts is "
                "held here, mean over the expert layers", buckets=share),
            "pairs": reg.histogram(
                "pio_moe_expert_pairs",
                "(Token, held expert) pairs one call computed, summed "
                "over its expert layers",
                buckets=tuple(float(2 ** i) for i in range(6, 21))),
            "buffers": reg.histogram(
                "pio_moe_buffers",
                "Buffers of (token, held expert) pairs one call's "
                "expert layer ran (ops/moe.buffer_pairs), mean over "
                "its expert layers",
                buckets=(0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 6.0, 8.0)),
            "gather": reg.histogram(
                "pio_moe_gather_combine_share",
                "Share of one call's expert layers that combined the "
                "experts' rows by a gather on the token side and not "
                "by a scatter-add (ops/moe.gather_combine)",
                buckets=share),
            "ssm_reset": reg.histogram(
                "pio_seq_ssm_reset_chunk_share",
                "Share of one call's chunks of the state-space scan "
                "(ssm_chunk tokens each) that hold a history's first "
                "event, where the carried state is cut and what lies "
                "before the event is masked", buckets=share),
            "ssm_in_place": reg.histogram(
                "pio_seq_ssm_in_place_share",
                "Share of one call's state-space scans that read x, B "
                "and C and wrote y where the mixer lays them, with no "
                "group-major copy (ops/ssm.in_place)", buckets=share),
            "retention_carry": reg.histogram(
                "pio_seq_retention_carry_chunk_share",
                "Share of one call's chunks of the retention kernel "
                "(retention_chunk tokens each) that read the state "
                "carried from the chunk before: those whose first token "
                "continues a history that is served", buckets=share),
        }
    return _SEQ_METRICS


def _packed_last(params, tokens, seg, start, last, *, cfg):
    """One call of the packed stack: tokens / seg / start [Tb], `last`
    [rows] the token index of each history's newest event -> ([rows,
    D] float32, the expert layers' counts)."""
    Tb, rows = tokens.shape[0], last.shape[0]
    live = seg != rows              # padding is segment `rows`
    idx = jnp.arange(Tb, dtype=jnp.int32) - start
    if cfg.positions:               # learned: slots of a right-aligned row
        seg_len = jnp.zeros((rows + 1,), jnp.int32).at[seg].add(1)[seg]
        idx = idx + cfg.positions - seg_len

    def attend(q, k, v, *, window, sink):
        return packed_attention(q, k, v, seg, start, window=window,
                                sink=sink, max_segment=cfg.max_history)

    with jax.named_scope("seq_stack"):
        x, stats = bb.forward(params, cfg, tokens, idx, attend, valid=live)
    return x[last], stats


class PackedEncoder:
    """The sequence model's serve plan: the weights pinned on the
    device and one executable a token bucket, compiled ahead of time
    (`warm`). A call packs histories in arrival order into stack calls
    of at most `max_batch_tokens` tokens and `rows` histories, pads
    each to the next bucket (the padding is one more segment that no
    history sees and no expert is loaded for), and returns each
    history's last-position vector. Steady state compiles nothing."""

    def __init__(self, model: SeqRecModel, *, rows: int):
        self.cfg = model.config
        self.params = device_params(model)
        self.rows = int(rows)
        self.buckets = tuple(sorted(self.cfg.token_buckets))
        if not self.buckets or self.buckets[-1] < self.cfg.max_history:
            raise ValueError(
                f"backbone {self.cfg.name!r}: token buckets "
                f"{self.buckets} do not hold a history of "
                f"{self.cfg.max_history}")
        self.max_tokens = min(self.cfg.max_batch_tokens or self.buckets[-1],
                              self.buckets[-1])
        self._exe: Dict[int, Any] = {}

    def warm(self) -> int:
        def seq_stack_call(params, tokens, seg, start, last):
            return _packed_last(params, tokens, seg, start, last,
                                cfg=self.cfg)

        fn = jax.jit(seq_stack_call)     # the trace names it by this
        compiled = 0
        for b in self.buckets:
            if b in self._exe:
                continue
            ints = jax.ShapeDtypeStruct((b,), np.int32)
            self._exe[b] = fn.lower(
                self.params, ints, ints, ints,
                jax.ShapeDtypeStruct((self.rows,), np.int32)).compile()
            compiled += 1
        return compiled

    def _calls(self, histories: Sequence[Sequence[int]]):
        """Arrival order, cut where the next history would pass the
        call's tokens or rows."""
        calls, cur, used = [], [], 0
        for h in histories:
            if cur and (used + len(h) > self.max_tokens
                        or len(cur) == self.rows):
                calls.append(cur)
                cur, used = [], 0
            cur.append(h)
            used += len(h)
        if cur:
            calls.append(cur)
        return calls

    def __call__(self, histories: Sequence[Sequence[int]]) -> np.ndarray:
        """histories: item indexes, oldest first, each 1 to max_history
        long -> [n, D] float32, one row a history."""
        metrics = _seq_metrics()
        launched = []
        for call in self._calls(histories):
            with trace.stage("seq_pack"):
                lens = np.fromiter(map(len, call), np.int64, len(call))
                n_tok = int(lens.sum())
                bucket = next(b for b in self.buckets if b >= n_tok)
                ends = np.cumsum(lens)
                tokens = np.zeros(bucket, np.int32)
                tokens[:n_tok] = np.concatenate(
                    [np.asarray(h, np.int32) for h in call])  # lint: ok
                seg = np.full(bucket, self.rows, np.int32)
                seg[:n_tok] = np.repeat(np.arange(len(call)), lens)
                start = np.full(bucket, n_tok, np.int32)
                start[:n_tok] = np.repeat(ends - lens, lens)
                last = np.zeros(self.rows, np.int32)
                last[:len(call)] = ends - 1
            with trace.stage("seq_launch"):
                exe = self._exe.get(bucket)
                if exe is None:
                    raise RuntimeError(
                        f"PackedEncoder bucket {bucket} not warmed; "
                        "call warm() at deploy time")
                launched.append((len(call), n_tok, bucket, lens,
                                 exe(self.params, tokens, seg, start,
                                     last)))
        out = []
        with trace.stage("seq_fetch"):
            fetched = [jax.device_get(x[-1]) for x in launched]
        for (n, n_tok, bucket, lens, _), (vecs, stats) in zip(launched,
                                                              fetched):
            out.append(vecs[:n])
            try:
                metrics["tokens"].observe(n_tok)
                metrics["pad"].observe(1.0 - n_tok / bucket)
                if self.cfg.ssm_chunk:      # from the pack's layout
                    chunk = self.cfg.ssm_chunk
                    firsts = np.cumsum(lens) - lens
                    metrics["ssm_reset"].observe(
                        len(np.unique(firsts // chunk)) * chunk / bucket)
                    # one rule for every state-space mixer of the stack
                    metrics["ssm_in_place"].observe(float(ssm.in_place(
                        self.cfg.ssm_heads // self.cfg.ssm_groups
                        * self.cfg.ssm_head_dim, self.cfg.ssm_state,
                        self.cfg.ssm_groups)))
                if self.cfg.retention_chunk:    # the kernel's own rule
                    chunk = self.cfg.retention_chunk
                    starts = np.arange(0, n_tok, chunk)
                    reads = len(starts) - np.isin(
                        starts, np.cumsum(lens) - lens).sum()
                    metrics["retention_carry"].observe(
                        reads * chunk / bucket)
                if stats is not None:
                    per = np.asarray(stats.expert_tokens, np.float64)
                    mean = per.mean(axis=1)
                    if (mean > 0).all():
                        metrics["load"].observe(
                            float((per.max(axis=1) / mean).mean()))
                    metrics["unrouted"].observe(float(
                        np.asarray(stats.unrouted).mean() / n_tok))
                    metrics["pairs"].observe(float(per.sum()))
                    cap = moe.buffer_pairs(
                        bucket, self.cfg.top_k, self.cfg.experts_held,
                        self.cfg.n_experts)
                    metrics["buffers"].observe(float(
                        np.ceil(per.sum(axis=1) / cap).mean()))
                    # one rule for every expert layer of the stack
                    metrics["gather"].observe(float(moe.gather_combine(
                        bucket, self.cfg.top_k, self.cfg.experts_held,
                        self.cfg.n_experts)))
            except Exception:
                pass  # metrics must never fail a serve call
        return np.concatenate(out).astype(np.float32)


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
