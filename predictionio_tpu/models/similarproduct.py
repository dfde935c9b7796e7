"""Similar-product template: implicit ALS + cooccurrence + like/dislike,
demonstrating a multi-algorithm engine.

Parity target: `examples/scala-parallel-similarproduct/
multi-events-multi-algos/`
  - DataSource reads `$set` item events (with `categories`) and `view` +
    `like`/`dislike` events (`DataSource.scala`)
  - ALSAlgorithm: MLlib implicit ALS on views (`ALSAlgorithm.scala:120`),
    query = set of liked items -> cosine-similar items, with category /
    whiteList / blackList filters and query items excluded
  - LikeAlgorithm: like=+1 / dislike=-1 implicit ALS
    (`LikeAlgorithm.scala:37-101`)
  - CooccurrenceAlgorithm: item-item cooccurrence counts
    (`CooccurrenceAlgorithm.scala:47-110`)
  - Serving averages scores per item across algorithms (`Serving.scala`)
  - wire: query `{"items": ["i1"], "num": 4}` ->
    `{"itemScores": [{"item": ..., "score": ...}]}`
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from predictionio_tpu.core import (
    Algorithm, DataSource, Engine, EngineFactory, IdentityPreparator,
    Params, RuntimeContext, Serving, register_engine,
)
from predictionio_tpu.data import store
from predictionio_tpu.ingest import BiMap, RatingColumns
from predictionio_tpu.ops import als
from predictionio_tpu.ops.cooccur import (
    CooccurrenceModel, top_cooccurrences_from_pairs,
)
from predictionio_tpu.ops.topk import NEG_INF, score_similar


@dataclass(frozen=True)
class Query(Params):
    items: Sequence[str] = ()
    num: int = 10
    categories: Optional[Sequence[str]] = None
    whiteList: Optional[Sequence[str]] = None
    blackList: Optional[Sequence[str]] = None


@dataclass(frozen=True)
class ItemScore:
    item: str
    score: float


@dataclass(frozen=True)
class PredictedResult:
    itemScores: Sequence[ItemScore] = ()


@dataclass
class TrainingData:
    """views + likes + item categories (the template's TrainingData)."""
    views: RatingColumns
    likes: RatingColumns           # rating +1 like / -1 dislike
    item_categories: Dict[str, List[str]]


@dataclass(frozen=True)
class DataSourceParams(Params):
    app_name: str = "default"
    channel: Optional[str] = None


class SimilarProductDataSource(DataSource):
    params_class = DataSourceParams

    def read_training(self, ctx: RuntimeContext) -> TrainingData:
        p = self.params
        views = store.rating_columns(
            ctx.registry, p.app_name, p.channel,
            event_names=["view"], value_spec={"*": 1.0})
        likes = store.rating_columns(
            ctx.registry, p.app_name, p.channel,
            event_names=["like", "dislike"],
            value_spec={"like": 1.0, "dislike": -1.0},
            dedup_last_wins=True)   # latest like/dislike wins (template doc)
        cats: Dict[str, List[str]] = {}
        props = store.aggregate_properties(
            ctx.registry, p.app_name, channel_name=p.channel,
            entity_type="item")
        for item_id, pm in props.items():
            c = pm.get_opt("categories")
            if c:
                cats[item_id] = list(c)
        return TrainingData(views, likes, cats)


def _resolve_filters(model_items: BiMap, item_categories,
                     query: Query) -> np.ndarray:
    """Allowed-item mask: categories/white/black lists + the query items
    themselves excluded (ALSAlgorithm.scala predict filters)."""
    from predictionio_tpu.models.common import resolve_item_mask
    query_ix = [ix for it in query.items
                if (ix := model_items.get(it)) is not None]
    return resolve_item_mask(
        model_items, item_categories, categories=query.categories,
        white_list=query.whiteList, black_list=query.blackList or (),
        extra_blacklist_ix=query_ix)


@dataclass
class SimilarModel:
    """Item factors + categories (the P2L productFeatures analog)."""
    item_factors: np.ndarray
    items: BiMap
    item_categories: Dict[str, List[str]]
    # user-side factors, kept since the streaming subsystem so fold-in
    # can run the item half-step against them; None on artifacts
    # trained before then (those force the full-scan path)
    user_factors: Optional[np.ndarray] = None
    users: Optional[BiMap] = None

    def sanity_check(self):
        assert np.isfinite(self.item_factors).all()


class _FactorSimilarityAlgorithm(Algorithm):
    """Shared predict: cosine top-k against the mean of query-item
    factors, one jit'd program per batch."""

    query_class = Query

    def predict(self, model: SimilarModel, query: Query) -> PredictedResult:
        return self.batch_predict(model, [(0, query)])[0][1]

    def warm_serving(self, model: SimilarModel, buckets,
                     mesh=None) -> int:
        """Deploy warmup: pin item factors device-resident and
        AOT-compile the per-bucket cosine-top-k executables, so the
        dense-mask serve path never consults the jit tracing cache.
        A configured serving mesh (or an over-capacity catalog) shards
        the factors row-wise (`ShardedBucketedSimilar`)."""
        from predictionio_tpu.ops.topk_sharded import similar_plan
        self._serve_plan = similar_plan(
            model.item_factors, k=Query().num, buckets=buckets,
            mesh=mesh)
        return self._serve_plan.warm()

    def batch_predict(self, model: SimilarModel,
                      queries: Sequence[Tuple[int, Query]]
                      ) -> List[Tuple[int, PredictedResult]]:
        out: List[Tuple[int, PredictedResult]] = []
        live = []
        for i, q in queries:
            ixs = [ix for it in q.items
                   if (ix := model.items.get(it)) is not None]
            if not ixs:   # no known query item -> empty (template logs warn)
                out.append((i, PredictedResult()))
            else:
                live.append((i, q, ixs))
        if not live:
            return out
        n_items = model.item_factors.shape[0]
        ks = [min(q.num, n_items) for _, q, _ in live]
        vecs = np.stack([model.item_factors[ixs].mean(axis=0)
                         for _, _, ixs in live])
        mask = np.concatenate(
            [_resolve_filters(model.items, model.item_categories, q)
             for _, q, _ in live], axis=0)
        scores, ixs = score_similar(
            getattr(self, "_serve_plan", None), vecs.astype(np.float32),
            model.item_factors, mask, ks)
        scores, ixs = np.asarray(scores), np.asarray(ixs)
        for row, (i, q, _) in enumerate(live):
            items = [ItemScore(model.items.inverse(int(ix)), float(s))
                     for s, ix in zip(scores[row], ixs[row])
                     if s > NEG_INF / 2][:q.num]
            out.append((i, PredictedResult(tuple(items))))
        return out

    def _fold(self, model: SimilarModel, fctx, *, event_names,
              value_spec, value_of,
              dedup_last_wins) -> Optional[SimilarModel]:
        """Shared streaming fold: implicit-ALS half-steps over the rows
        this algorithm's delta events touched (user rows vs fixed item
        factors, then item rows vs the updated user factors). Artifacts
        trained before the streaming subsystem carry no user-side
        factors and fall back to the full-scan path."""
        from predictionio_tpu.data.storage.base import DeltaInvalidated
        from predictionio_tpu.streaming.updaters import (
            fold_als_items, fold_als_users,
        )
        if model.user_factors is None or model.users is None:
            raise DeltaInvalidated(
                "artifact predates streaming (no user-side factors); "
                "full rebuild required")
        p = self.params
        cols = fctx.delta_columns(
            entity_type="user", event_names=list(event_names),
            value_spec=value_spec, require_target=True)
        if cols.n == 0:
            return None
        uf, users2, _ = fold_als_users(
            fctx, model.users, model.items, model.user_factors,
            model.item_factors, list(cols.entities),
            event_names=event_names, value_of=value_of,
            dedup_last_wins=dedup_last_wins, reg=p.lambda_,
            implicit=True, alpha=p.alpha)
        yf, _ = fold_als_items(
            fctx, users2, model.items, uf, model.item_factors,
            list(cols.targets), event_names=event_names,
            value_of=value_of, dedup_last_wins=dedup_last_wins,
            reg=p.lambda_, implicit=True, alpha=p.alpha)
        return SimilarModel(yf, model.items, model.item_categories,
                            user_factors=uf, users=users2)


@dataclass(frozen=True)
class ALSParams(Params):
    rank: int = 10
    num_iterations: int = 10
    lambda_: float = 0.01
    alpha: float = 1.0
    seed: Optional[int] = None


class ALSAlgorithm(_FactorSimilarityAlgorithm):
    """Implicit ALS on view events (ALSAlgorithm.scala:120)."""

    params_class = ALSParams

    def train(self, ctx: RuntimeContext, pd: TrainingData) -> SimilarModel:
        p = self.params
        if pd.views.n == 0:
            raise ValueError("No view events found "
                             "(ALSAlgorithm.scala require non-empty)")
        x, y = als.als_train(
            pd.views, rank=p.rank, iterations=p.num_iterations,
            reg=p.lambda_, implicit=True, alpha=p.alpha,
            seed=p.seed if p.seed is not None else 0, mesh=ctx.mesh,
            timings=ctx.phase_timings)
        return SimilarModel(y, pd.views.items, pd.item_categories,
                            user_factors=x, users=pd.views.users)

    def fold_in(self, model: SimilarModel, delta,
                fctx) -> Optional[SimilarModel]:
        """Streaming fold-in on the delta's view events."""
        return self._fold(model, fctx, event_names=["view"],
                          value_spec={"*": 1.0},
                          value_of=lambda ev: 1.0,
                          dedup_last_wins=False)


class LikeAlgorithm(_FactorSimilarityAlgorithm):
    """Implicit ALS on like(+1)/dislike(-1) events
    (LikeAlgorithm.scala:37-101)."""

    params_class = ALSParams

    def train(self, ctx: RuntimeContext, pd: TrainingData) -> SimilarModel:
        p = self.params
        if pd.likes.n == 0:
            raise ValueError("No like/dislike events found")
        x, y = als.als_train(
            pd.likes, rank=p.rank, iterations=p.num_iterations,
            reg=p.lambda_, implicit=True, alpha=p.alpha,
            seed=p.seed if p.seed is not None else 0, mesh=ctx.mesh,
            timings=ctx.phase_timings)
        return SimilarModel(y, pd.likes.items, pd.item_categories,
                            user_factors=x, users=pd.likes.users)

    def fold_in(self, model: SimilarModel, delta,
                fctx) -> Optional[SimilarModel]:
        """Streaming fold-in on like/dislike events (latest wins,
        matching the training dedup)."""
        return self._fold(
            model, fctx, event_names=["like", "dislike"],
            value_spec={"like": 1.0, "dislike": -1.0},
            value_of=lambda ev: 1.0 if ev.event == "like" else -1.0,
            dedup_last_wins=True)


@dataclass(frozen=True)
class CooccurrenceParams(Params):
    n: int = 20   # cooccurrences kept per item
    # optional per-user distinct-item cap (Mahout --maxPrefsPerUser);
    # None = exact parity with the reference self-join
    max_items_per_user: Optional[int] = None


@dataclass
class CoocModel:
    top: CooccurrenceModel
    items: BiMap
    item_categories: Dict[str, List[str]]


class CooccurrenceAlgorithm(Algorithm):
    """(CooccurrenceAlgorithm.scala:47-110)"""

    params_class = CooccurrenceParams
    query_class = Query

    def train(self, ctx: RuntimeContext, pd: TrainingData) -> CoocModel:
        views = pd.views
        top = top_cooccurrences_from_pairs(
            views.user_ix, views.item_ix,
            len(views.users), len(views.items), self.params.n,
            max_items_per_user=self.params.max_items_per_user)
        return CoocModel(top, views.items, pd.item_categories)

    def fold_in(self, model: CoocModel, delta,
                fctx) -> Optional[CoocModel]:
        """Streaming count-merge fold: for each delta-touched user, an
        item is NEWLY connected when its full-history view count equals
        its delta view count (every view of it by that user is inside
        the delta), and each new item pairs once with the user's other
        distinct items — exactly the pairs the reference self-join
        would gain. Increments merge into the stored top-N lists via
        `ops.cooccur.merge_pair_counts` (its docstring states the
        truncation approximation; full retrain is ground truth)."""
        from predictionio_tpu.data.storage.base import DeltaInvalidated
        from predictionio_tpu.ops.cooccur import merge_pair_counts
        cols = fctx.delta_columns(
            entity_type="user", event_names=["view"],
            value_spec={"*": 1.0}, require_target=True)
        if cols.n == 0:
            return None
        delta_cnt: Dict[str, Dict[str, int]] = {}
        for eix, tix in zip(cols.entity_ix, cols.target_ix):
            u = cols.entities[int(eix)]
            it = cols.targets[int(tix)]
            d = delta_cnt.setdefault(u, {})
            d[it] = d.get(it, 0) + 1
        pairs: Dict[Tuple[int, int], float] = {}
        for u, dcnt in delta_cnt.items():
            full: Dict[int, int] = {}
            for ev in fctx.user_history(u, ["view"]):
                ix = model.items.get(ev.target_entity_id)
                if ix is None:
                    raise DeltaInvalidated(
                        f"user {u!r} viewed unknown item "
                        f"{ev.target_entity_id!r}; full rebuild "
                        "required")
                full[ix] = full.get(ix, 0) + 1
            new: List[int] = []
            for it, c in dcnt.items():
                ix = model.items.get(it)
                if ix is None:
                    raise DeltaInvalidated(
                        f"new item {it!r} in delta; full rebuild "
                        "required")
                if full.get(ix, 0) == c:
                    new.append(ix)
            new_set = set(new)
            old = [ix for ix in full if ix not in new_set]
            for ai, a in enumerate(new):
                for b in old + new[ai + 1:]:
                    key = (a, b) if a < b else (b, a)
                    pairs[key] = pairs.get(key, 0.0) + 1.0
        if not pairs:
            return None
        return CoocModel(merge_pair_counts(model.top, pairs),
                         model.items, model.item_categories)

    def predict(self, model: CoocModel, query: Query) -> PredictedResult:
        n_items = len(model.items)
        scores = np.zeros(n_items, np.float64)
        for it in query.items:
            ix = model.items.get(it)
            if ix is None:
                continue
            scores[model.top.top_items[ix]] += model.top.top_counts[ix]
        mask = _resolve_filters(model.items, model.item_categories, query)[0]
        scores[~mask] = -np.inf
        order = np.argsort(-scores)[:query.num]
        items = [ItemScore(model.items.inverse(int(ix)), float(scores[ix]))
                 for ix in order if np.isfinite(scores[ix]) and scores[ix] > 0]
        return PredictedResult(tuple(items))


class ScoreAverageServing(Serving):
    """Average the score per item across algorithms (Serving.scala of
    multi-events-multi-algos)."""

    def serve(self, query: Query,
              predictions: Sequence[PredictedResult]) -> PredictedResult:
        sums: Dict[str, float] = {}
        counts: Dict[str, int] = {}
        for p in predictions:
            for s in p.itemScores:
                sums[s.item] = sums.get(s.item, 0.0) + s.score
                counts[s.item] = counts.get(s.item, 0) + 1
        averaged = [ItemScore(item, sums[item] / counts[item])
                    for item in sums]
        averaged.sort(key=lambda s: -s.score)
        return PredictedResult(tuple(averaged[:query.num]))


class SimilarProductEngine(EngineFactory):
    @classmethod
    def apply(cls) -> Engine:
        return Engine(
            data_source=SimilarProductDataSource,
            preparator=IdentityPreparator,
            algorithms={"als": ALSAlgorithm, "": ALSAlgorithm,
                        "likealgo": LikeAlgorithm,
                        "cooccurrence": CooccurrenceAlgorithm},
            serving=ScoreAverageServing,
        )


def engine() -> Engine:
    return SimilarProductEngine.apply()


register_engine("similarproduct", SimilarProductEngine)
