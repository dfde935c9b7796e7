"""Sequential recommender template (new capability).

No reference analog — the reference's recommenders are order-blind
(ALS over a rating matrix); this template predicts the NEXT item from
the ORDER of a user's events with a causal transformer
(`ops/seqrec.py`), the framework's long-context / sequence-parallel
proof point (ring attention over the mesh "sp" axis).

Uses the recommendation template's event shapes and query/result wire
format (swap `"engineFactory": "recommendation"` for `"seqrec"` in
engine.json and retrain). Serving re-reads the user's RECENT events
from the store at query time — the e-commerce template's
serve-time-read pattern (ECommAlgorithm.scala:331-430) — so a user's
newest activity influences their very next recommendation without
retraining.

The causal transformer is a BACKBONE (`ops/backbone.py`): `backbone:
"sasrec"` is the template's own small block built from `dim` /
`n_heads` / `n_layers`; anything else is a configuration file, a public
architecture's stack in its own config keys, the item catalog as its
vocabulary. Whatever the backbone, `batch_predict` packs the batch's
histories into a few token buckets compiled at deploy
(`ops/seqrec.PackedEncoder`) and scores the last positions through the
catalog top-k serve plan (`ops/topk.py`), as ALS scores its users.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import numpy as np

from predictionio_tpu.core import (
    Algorithm, DataSource, Engine, EngineFactory, FirstServing,
    IdentityPreparator, Params, RuntimeContext, register_engine,
)
from predictionio_tpu.data import store
from predictionio_tpu.ingest import BiMap, RatingColumns
from predictionio_tpu.models.common import score_and_rank
from predictionio_tpu.models.recommendation import PredictedResult, Query
from predictionio_tpu.obs import trace
from predictionio_tpu.ops.seqrec import (
    PackedEncoder, SeqRecModel, build_sequences, seqrec_train,
)


@dataclass(frozen=True)
class DataSourceParams(Params):
    app_name: str = "default"
    channel: Optional[str] = None
    event_names: Sequence[str] = ("view", "rate", "buy")


class SeqRecDataSource(DataSource):
    params_class = DataSourceParams

    def read_training(self, ctx: RuntimeContext) -> RatingColumns:
        p = self.params
        return store.rating_columns(
            ctx.registry, p.app_name, p.channel,
            event_names=list(p.event_names), value_spec={"*": 1.0})


@dataclass
class SeqRecServingModel:
    net: SeqRecModel
    users: BiMap
    items: BiMap

    def sanity_check(self):
        self.net.sanity_check()


@dataclass(frozen=True)
class SeqRecParams(Params):
    app_name: str = "default"           # serve-time history reads
    channel: Optional[str] = None
    event_names: Sequence[str] = ("view", "rate", "buy")
    seq_len: int = 32
    dim: int = 64
    n_heads: int = 2
    n_layers: int = 2
    batch_size: int = 256
    epochs: int = 20
    lr: float = 3e-3
    temperature: float = 0.07
    seed: Optional[int] = None
    # the layer stack: `sasrec` (built from dim / n_heads / n_layers
    # above) or a configuration file (ops/backbone.config_from_json)
    backbone: str = "sasrec"


class SeqRecAlgorithm(Algorithm):
    params_class = SeqRecParams
    query_class = Query

    def train(self, ctx: RuntimeContext,
              pd: RatingColumns) -> SeqRecServingModel:
        p = self.params
        self._serving_ctx = ctx
        if pd.n == 0:
            raise ValueError("No interaction events found")
        seqs, targets = build_sequences(
            pd.user_ix, pd.item_ix, pd.t_millis,
            n_items=len(pd.items), seq_len=p.seq_len)
        if not len(seqs):
            raise ValueError(
                "No user has >= 2 events; sequences cannot be built")
        bsz = min(p.batch_size, len(seqs))
        net = seqrec_train(
            seqs, targets, n_items=len(pd.items), seq_len=p.seq_len,
            dim=p.dim, n_heads=p.n_heads, n_layers=p.n_layers,
            batch_size=bsz, epochs=p.epochs, lr=p.lr,
            temperature=p.temperature,
            seed=p.seed if p.seed is not None else 0, mesh=ctx.mesh,
            backbone=p.backbone)
        return SeqRecServingModel(net, pd.users, pd.items)

    def fold_in(self, model: SeqRecServingModel, delta,
                fctx) -> Optional[SeqRecServingModel]:
        """Streaming fold-in: ONE warm-start epoch from the previous
        transformer weights over sequences rebuilt from the full event
        set (adam restarts fresh — a mini-epoch, not a retrain; the
        full re-read is the cost ceiling, the delta only gates the
        run). New ITEMS invalidate — the tied item table's shape is
        baked into the net. New users are fine: serving reads each
        user's history at query time, so they never index the net."""
        from predictionio_tpu.data.storage.base import DeltaInvalidated
        p = self.params
        cols = fctx.delta_columns(
            entity_type="user", event_names=list(p.event_names),
            value_spec={"*": 1.0}, require_target=True)
        if cols.n == 0:
            return None
        full = fctx.store.scan_columns(
            fctx.app_id, fctx.channel_id, entity_type="user",
            event_names=list(p.event_names), value_spec={"*": 1.0},
            require_target=True)
        i_of = np.array([model.items.get(t, -1) for t in full.targets],
                        np.int64)
        if (i_of < 0).any():
            raise DeltaInvalidated(
                "new items since train: the tied item-table shape is "
                "baked into the net; full rebuild required")
        seqs, targets = build_sequences(
            full.entity_ix.astype(np.int64), i_of[full.target_ix],
            full.t_millis, n_items=model.net.n_items,
            seq_len=model.net.seq_len)
        if not len(seqs):
            return None
        bsz = min(p.batch_size, len(seqs))
        net = seqrec_train(
            seqs, targets, n_items=model.net.n_items,
            seq_len=model.net.seq_len, dim=p.dim, n_heads=p.n_heads,
            n_layers=p.n_layers, batch_size=bsz, epochs=1, lr=p.lr,
            temperature=p.temperature,
            seed=p.seed if p.seed is not None else 0, mesh=fctx.mesh,
            init_params=model.net.params, backbone=p.backbone)
        return SeqRecServingModel(net, model.users, model.items)

    # -- serving -------------------------------------------------------------
    def _ctx(self) -> RuntimeContext:
        ctx = getattr(self, "_serving_ctx", None)
        if ctx is None:
            raise RuntimeError(
                "SeqRecAlgorithm.predict needs a serving context for "
                "its event-store reads; train/deploy through the Engine "
                "workflow, or call with_serving_context(ctx) first")
        return ctx

    def with_serving_context(self, ctx: RuntimeContext) -> None:
        self._serving_ctx = ctx

    def _history(self, model: SeqRecServingModel, user: str,
                 keep: int) -> List[int]:
        """The user's `keep` most recent item ids (store read, newest last).
        Reads a LARGER window than the model takes before filtering: the
        model's item map is frozen at training, so a burst of recent
        events on post-training items must evict into older mappable
        history, not empty it (history is this model's only input)."""
        p = self.params
        try:
            events = list(store.find_by_entity(
                self._ctx().registry, p.app_name, channel_name=p.channel,
                entity_type="user", entity_id=user,
                event_names=list(p.event_names),
                limit=4 * keep, latest_first=True))
        except store.AppNotFoundError:
            return []
        hist = [ix for e in reversed(events)
                if e.target_entity_id is not None
                and (ix := model.items.get(e.target_entity_id)) is not None]
        return hist[-keep:]

    def predict(self, model: SeqRecServingModel,
                query: Query) -> PredictedResult:
        return self.batch_predict(model, [(0, query)])[0][1]

    def _plans(self, model: SeqRecServingModel, buckets=(1,), mesh=None):
        """The serve plans of this model: the packed encoder over the
        stack and the catalog top-k plan over the head's rows (the one
        ALS serves its item factors with). Built once a model, the
        encoder's token buckets compiled with it; a deploy builds them in
        `warm_serving` (which also compiles the plan's batch buckets), a
        bare `predict` on first use."""
        plans = getattr(self, "_serve_plans", None)
        if plans is None or plans[0] is not model:
            from predictionio_tpu.ops.topk_sharded import serve_plan
            encoder = PackedEncoder(model.net, rows=max(buckets))
            encoder.warm()
            plans = (model, encoder,
                     serve_plan(model.net.item_emb, k=Query(user="").num,
                                buckets=buckets, banned_width=64,
                                mesh=mesh))
            self._serve_plans = plans
        return plans[1], plans[2]

    def serve_plans(self) -> tuple:
        plans = getattr(self, "_serve_plans", None)
        return () if plans is None else (plans[2],)

    def warm_serving(self, model: SeqRecServingModel, buckets,
                     mesh=None) -> int:
        """Deploy warm-up: pin the stack's weights and the head's rows
        on the device and compile, ahead of time, one executable a
        token bucket and one a batch bucket of the top-k plan. After it
        no query compiles."""
        self._serve_plans = None
        encoder, plan = self._plans(model, tuple(buckets), mesh)
        return len(encoder.buckets) + plan.warm()

    def batch_predict(self, model: SeqRecServingModel,
                      queries: Sequence[Tuple[int, Query]]
                      ) -> List[Tuple[int, PredictedResult]]:
        """Histories from the store, packed through the stack, the last
        positions scored by the catalog top-k plan. Stages of the batch
        cycle (obs/trace.stage): `history` (store read to item
        indexes), the encoder's `seq_pack` / `seq_launch` /
        `seq_fetch`, then `score_and_rank`'s."""
        out: List[Tuple[int, PredictedResult]] = []
        live = []
        with trace.stage("history"):
            keep = model.net.config.max_history
            for i, q in queries:
                hist = self._history(model, q.user, keep)
                if not hist:
                    out.append((i, PredictedResult()))
                else:
                    live.append((i, q, hist))
        if not live:
            return out
        encoder, plan = self._plans(model)
        vecs = encoder([h for _, _, h in live])
        return out + score_and_rank(vecs, model.net.item_emb, model.items,
                                    live, plan=plan)


class SeqRecEngine(EngineFactory):
    @classmethod
    def apply(cls) -> Engine:
        return Engine(
            data_source=SeqRecDataSource,
            preparator=IdentityPreparator,
            algorithms={"seqrec": SeqRecAlgorithm, "": SeqRecAlgorithm},
            serving=FirstServing,
        )


def engine() -> Engine:
    return SeqRecEngine.apply()


register_engine("seqrec", SeqRecEngine)
