"""E-commerce recommendation template: implicit ALS with serving-time
constraints and popularity fallback.

Parity target: `examples/scala-parallel-ecommercerecommendation/
adjust-score/src/main/scala/ECommAlgorithm.scala`
  - train: implicit ALS on view events + buy-count popularity
    (`train:90-160`, `trainDefault:214+`)
  - three-way predict (`predict:331-430`):
      known user  -> dot(user vector, item vectors)   (predictKnownUser:469)
      unknown user-> cosine to recently viewed items  (predictSimilar:539)
      no signal   -> popularity (buy counts)          (predictDefault:506)
  - serving-time event-store reads inside predict: the user's seen items
    (view/buy events) and the latest `$set` of constraint entity
    `unavailableItems` (`:331-430`) — the reference does per-request
    LEventStore reads with 200ms timeouts; here the same reads hit the
    local store synchronously
  - filters: categories, whiteList, blackList, seen, unavailable
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import numpy as np

from predictionio_tpu.core import (
    Algorithm, DataSource, Engine, EngineFactory, FirstServing,
    IdentityPreparator, Params, RuntimeContext, register_engine,
)
from predictionio_tpu.data import store
from predictionio_tpu.ingest import BiMap, RatingColumns
from predictionio_tpu.ops import als
from predictionio_tpu.ops.topk import (
    NEG_INF, score_banned, topk_scores, topk_similar,
)


@dataclass(frozen=True)
class Query(Params):
    user: str = ""
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
    views: RatingColumns
    buys: RatingColumns
    item_categories: Dict[str, List[str]]


@dataclass(frozen=True)
class DataSourceParams(Params):
    app_name: str = "default"
    channel: Optional[str] = None


class ECommDataSource(DataSource):
    params_class = DataSourceParams

    def read_training(self, ctx: RuntimeContext) -> TrainingData:
        p = self.params
        views = store.rating_columns(
            ctx.registry, p.app_name, p.channel,
            event_names=["view"], value_spec={"*": 1.0})
        # buys share the view BiMaps so popularity aligns with factors
        buys = store.rating_columns(
            ctx.registry, p.app_name, p.channel,
            event_names=["buy"], value_spec={"*": 1.0},
            users=views.users, items=views.items)
        cats: Dict[str, List[str]] = {}
        props = store.aggregate_properties(
            ctx.registry, p.app_name, channel_name=p.channel,
            entity_type="item")
        for item_id, pm in props.items():
            c = pm.get_opt("categories")
            if c:
                cats[item_id] = list(c)
        return TrainingData(views, buys, cats)


@dataclass
class ECommModel:
    user_factors: np.ndarray
    item_factors: np.ndarray
    users: BiMap
    items: BiMap
    popularity: np.ndarray          # [n_items] buy counts (trainDefault)
    item_categories: Dict[str, List[str]]

    def sanity_check(self):
        assert np.isfinite(self.user_factors).all()
        assert np.isfinite(self.item_factors).all()


@dataclass(frozen=True)
class ECommParams(Params):
    app_name: str = "default"
    channel: Optional[str] = None
    unseen_only: bool = True
    seen_events: Sequence[str] = ("view", "buy")
    similar_events: Sequence[str] = ("view",)
    num_recent_events: int = 10
    rank: int = 10
    num_iterations: int = 10
    lambda_: float = 0.01
    alpha: float = 1.0
    seed: Optional[int] = None
    # None = solver default; raise for large implicit problems where the
    # normal-equation CG needs more sweeps to converge (high alpha makes
    # the preference system stiff)
    cg_iters: Optional[int] = None


class ECommAlgorithm(Algorithm):
    params_class = ECommParams
    query_class = Query

    def train(self, ctx: RuntimeContext, pd: TrainingData) -> ECommModel:
        # the training context also serves direct train->predict use;
        # prepare_deploy rebinds a fresh one at deploy time
        self._serving_ctx = ctx
        p = self.params
        if pd.views.n == 0:
            raise ValueError("No view events found "
                             "(ECommAlgorithm.train require non-empty)")
        extra = {} if p.cg_iters is None else {"cg_iters": p.cg_iters}
        # timings= lands pack/solve/fetch phases AND solver_residual in
        # the run's phase report — the scale bench's convergence gate
        # reads the residual from there, so omitting this silently
        # disarms it (the r05 runs shipped a 2.58e-1 residual unnoticed)
        x, y = als.als_train(
            pd.views, rank=p.rank, iterations=p.num_iterations,
            reg=p.lambda_, implicit=True, alpha=p.alpha,
            seed=p.seed if p.seed is not None else 0, mesh=ctx.mesh,
            timings=ctx.phase_timings, **extra)
        pop = np.zeros(len(pd.views.items), np.float32)
        np.add.at(pop, pd.buys.item_ix, 1.0)
        return ECommModel(x, y, pd.views.users, pd.views.items, pop,
                          pd.item_categories)

    # -- serving-time store reads (ECommAlgorithm.scala:331-430) -----------
    def _seen_items(self, ctx: RuntimeContext, user: str) -> List[str]:
        p = self.params
        if not p.unseen_only:
            return []
        try:
            return [e.target_entity_id for e in store.find_by_entity(
                ctx.registry, p.app_name, channel_name=p.channel,
                entity_type="user", entity_id=user,
                event_names=list(p.seen_events))
                if e.target_entity_id]
        except store.AppNotFoundError:
            return []

    def _unavailable_items(self, ctx: RuntimeContext) -> List[str]:
        try:
            events = list(store.find_by_entity(
                ctx.registry, self.params.app_name,
                channel_name=self.params.channel,
                entity_type="constraint", entity_id="unavailableItems",
                event_names=["$set"], limit=1, latest_first=True))
        except store.AppNotFoundError:
            return []
        if not events:
            return []
        return list(events[0].properties.get_or_else("items", []))

    def _recent_items(self, ctx: RuntimeContext, user: str) -> List[str]:
        p = self.params
        try:
            return [e.target_entity_id for e in store.find_by_entity(
                ctx.registry, p.app_name, channel_name=p.channel,
                entity_type="user", entity_id=user,
                event_names=list(p.similar_events),
                limit=p.num_recent_events, latest_first=True)
                if e.target_entity_id]
        except store.AppNotFoundError:
            return []

    def _mask(self, ctx: RuntimeContext, model: ECommModel, query: Query,
              unavailable: Sequence[str]) -> np.ndarray:
        from predictionio_tpu.models.common import resolve_item_mask
        extra = [ix for it in unavailable
                 if (ix := model.items.get(it)) is not None]
        extra += [ix for it in self._seen_items(ctx, query.user)
                  if (ix := model.items.get(it)) is not None]
        return resolve_item_mask(
            model.items, model.item_categories, categories=query.categories,
            white_list=query.whiteList, black_list=query.blackList or (),
            extra_blacklist_ix=extra)

    def _ctx(self) -> RuntimeContext:
        ctx = getattr(self, "_serving_ctx", None)
        if ctx is None:
            raise RuntimeError(
                "ECommAlgorithm.predict needs a serving context for its "
                "event-store reads; train/deploy through the Engine "
                "workflow, or call with_serving_context(ctx) first")
        return ctx

    def predict(self, model: ECommModel, query: Query) -> PredictedResult:
        ctx = self._ctx()
        return self._predict_one(ctx, model, query,
                                 self._unavailable_items(ctx))

    def _predict_one(self, ctx: RuntimeContext, model: ECommModel,
                     query: Query,
                     unavailable: Sequence[str]) -> PredictedResult:
        mask = self._mask(ctx, model, query, unavailable)
        n_items = model.item_factors.shape[0]
        k = min(query.num, n_items)
        u_ix = model.users.get(query.user)
        if u_ix is not None and np.any(model.user_factors[u_ix]):
            scores, ixs = topk_scores(
                model.user_factors[u_ix][None, :].astype(np.float32),
                model.item_factors, mask, k=k)           # predictKnownUser
        else:
            recent = [ix for it in self._recent_items(ctx, query.user)
                      if (ix := model.items.get(it)) is not None]
            if recent:
                vec = model.item_factors[recent].mean(axis=0)
                scores, ixs = topk_similar(
                    vec[None, :].astype(np.float32),
                    model.item_factors, mask, k=k)       # predictSimilar
            else:
                scores, ixs = topk_scores(
                    np.ones((1, 1), np.float32),
                    model.popularity[:, None], mask, k=k)  # predictDefault
        scores, ixs = np.asarray(scores)[0], np.asarray(ixs)[0]
        items = [ItemScore(model.items.inverse(int(ix)), float(s))
                 for s, ix in zip(scores, ixs) if s > NEG_INF / 2]
        return PredictedResult(tuple(items))

    def warm_serving(self, model: ECommModel, buckets,
                     mesh=None) -> int:
        """Build the deploy-time serving plan: item factors pinned device
        resident, one AOT executable per batch bucket, banned width sized
        to the CURRENT unavailableItems constraint plus headroom for
        per-user seen/blackList indices. A configured serving mesh (or an
        over-capacity catalog) shards the factors row-wise
        (`ShardedBucketedTopK`); banned ids stay global either way."""
        from predictionio_tpu.ops.topk_sharded import serve_plan
        ctx = getattr(self, "_serving_ctx", None)
        n_unavail = len(self._unavailable_items(ctx)) if ctx else 0
        self._serve_plan = serve_plan(
            model.item_factors, k=Query().num, buckets=buckets,
            banned_width=max(256, n_unavail + 128), mesh=mesh)
        return self._serve_plan.warm()

    def fold_in(self, model: ECommModel, delta, fctx) -> ECommModel:
        """Streaming fold-in: implicit-ALS half-steps over the rows the
        delta's VIEW events touched, plus a buy-count merge into the
        popularity fallback. The view re-scan derives the touched sets
        under this template's own spec — a buy of a never-viewed item
        is outside the factor model (train builds BiMaps from views)
        and must not force a full rebuild. Count-merged popularity may
        over-count events racing a full rebuild; the periodic full
        retrain remains ground truth."""
        from predictionio_tpu.streaming.updaters import (
            fold_als_items, fold_als_users,
        )
        p = self.params
        views = fctx.delta_columns(
            entity_type="user", event_names=["view"],
            value_spec={"*": 1.0}, require_target=True)
        pop = model.popularity.copy()
        buys = fctx.delta_columns(
            entity_type="user", event_names=["buy"],
            value_spec={"*": 1.0}, require_target=True)
        for tix in buys.target_ix:
            ix = model.items.get(buys.targets[int(tix)])
            if ix is not None:
                pop[ix] += 1.0
        if views.n == 0:
            if buys.n == 0:
                return None
            return ECommModel(model.user_factors, model.item_factors,
                              model.users, model.items, pop,
                              model.item_categories)

        def value_of(ev):
            return 1.0

        uf, users2, _ = fold_als_users(
            fctx, model.users, model.items, model.user_factors,
            model.item_factors, list(views.entities),
            event_names=["view"], value_of=value_of,
            dedup_last_wins=False, reg=p.lambda_, implicit=True,
            alpha=p.alpha)
        yf, _ = fold_als_items(
            fctx, users2, model.items, uf, model.item_factors,
            list(views.targets), event_names=["view"],
            value_of=value_of, dedup_last_wins=False, reg=p.lambda_,
            implicit=True, alpha=p.alpha)
        return ECommModel(uf, yf, users2, model.items, pop,
                          model.item_categories)

    def batch_predict(self, model, queries):
        """Batched serve path. Known-user queries without dense-mask
        needs (no categories/whiteList) coalesce into ONE banned-index
        top-k dispatch (`score_banned`: the deploy-warmed plan for the
        rows that fit it, the generic path for the rest). Everything
        else (unknown users, dense filters) falls back to the per-query
        three-way predict."""
        # the unavailableItems constraint read is shared across the batch
        ctx = self._ctx()
        unavailable = self._unavailable_items(ctx)
        unavail_ix = [ix for it in unavailable
                      if (ix := model.items.get(it)) is not None]
        n_items = model.item_factors.shape[0]
        batched = []    # (orig_i, query, user_ix, banned indices)
        out = []
        for i, q in queries:
            u_ix = model.users.get(q.user)
            if (q.categories is None and q.whiteList is None
                    and u_ix is not None
                    and np.any(model.user_factors[u_ix])):
                banned = list(unavail_ix)
                banned += [ix for it in self._seen_items(ctx, q.user)
                           if (ix := model.items.get(it)) is not None]
                banned += [ix for it in (q.blackList or ())
                           if (ix := model.items.get(it)) is not None]
                batched.append((i, q, u_ix, banned))
            else:
                out.append((i, self._predict_one(ctx, model, q,
                                                 unavailable)))
        if not batched:
            return out
        vecs = model.user_factors[
            np.array([u for _, _, u, _ in batched])].astype(np.float32)
        scores, ixs = score_banned(
            getattr(self, "_serve_plan", None), vecs, model.item_factors,
            [b for _, _, _, b in batched],
            [min(q.num, n_items) for _, q, _, _ in batched])
        for row, (i, q, _, _) in enumerate(batched):
            items = []
            for s, ix in zip(scores[row], ixs[row]):
                if s <= NEG_INF / 2 or len(items) >= q.num:
                    continue
                items.append(ItemScore(model.items.inverse(int(ix)),
                                       float(s)))
            out.append((i, PredictedResult(tuple(items))))
        return out

    def with_serving_context(self, ctx: RuntimeContext) -> "ECommAlgorithm":
        self._serving_ctx = ctx
        return self


class ECommerceEngine(EngineFactory):
    @classmethod
    def apply(cls) -> Engine:
        return Engine(
            data_source=ECommDataSource,
            preparator=IdentityPreparator,
            algorithms={"ecomm": ECommAlgorithm, "": ECommAlgorithm},
            serving=FirstServing,
        )


def engine() -> Engine:
    return ECommerceEngine.apply()


register_engine("ecommerce", ECommerceEngine)
