"""Recommendation template: explicit ALS with blacklist filtering.

Parity target: `examples/scala-parallel-recommendation/blacklist-items/`
  - DataSource reads `rate` and `buy` events, mapping buy -> rating 4.0
    (`DataSource.scala:43-72`), with k-fold `readEval`
    (`DataSource.scala:76-101`)
  - ALSAlgorithm wraps MLlib explicit ALS (`ALSAlgorithm.scala:51-93`);
    here `ops.als.als_train` — degree-bucketed batched-Cholesky ALS
  - predict = top-N with blacklist filter, empty result for unknown users
    (`ALSAlgorithm.scala:96-112`); batchPredict for eval (`:115-150`)
  - wire format: query `{"user": "1", "num": 4}` ->
    `{"itemScores": [{"item": "i", "score": s}]}`

Query batching is the TPU win: `batch_predict` scores a whole query batch
in one jit'd matmul+top_k, where the reference loops driver-side.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import numpy as np

from predictionio_tpu.core import (
    Algorithm, DataSource, Engine, EngineFactory, FirstServing,
    IdentityPreparator, OptionAverageMetric, Params, RuntimeContext,
    register_engine,
)
from predictionio_tpu.data import store
from predictionio_tpu.ingest import RatingColumns
from predictionio_tpu.models.common import score_and_rank
from predictionio_tpu.obs import trace
from predictionio_tpu.ops import als


# -- queries and results (wire-format parity) -------------------------------

@dataclass(frozen=True)
class Query(Params):
    user: str
    num: int = 10
    blackList: Optional[Sequence[str]] = None
    whiteList: Optional[Sequence[str]] = None


@dataclass(frozen=True)
class ItemScore:
    item: str
    score: float


@dataclass(frozen=True)
class PredictedResult:
    itemScores: Sequence[ItemScore] = ()


@dataclass(frozen=True)
class ActualResult:
    """Test-fold ratings of the query's user (Evaluation.scala)."""
    ratings: Sequence[Tuple[str, float]] = ()


# -- data source ------------------------------------------------------------

@dataclass(frozen=True)
class EvalParams(Params):
    """(DataSourceEvalParams, DataSource.scala:30)"""
    k_fold: int = 3
    query_num: int = 10


@dataclass(frozen=True)
class DataSourceParams(Params):
    app_name: str = "default"
    channel: Optional[str] = None
    buy_rating: float = 4.0
    eval_params: Optional[EvalParams] = None


class RecommendationDataSource(DataSource):
    params_class = DataSourceParams

    def _ratings(self, ctx: RuntimeContext) -> RatingColumns:
        p = self.params
        # columnar ingest path — same output as the Event iterator with
        # rating_of {rate -> properties.rating, buy -> buy_rating}
        # (DataSource.scala:61-66), but scanned without Event objects
        return store.rating_columns(
            ctx.registry, p.app_name, p.channel,
            event_names=["rate", "buy"],
            value_spec={"rate": ("prop", "rating"),
                        "buy": float(p.buy_rating)},
            dedup_last_wins=True)

    def read_training(self, ctx: RuntimeContext) -> RatingColumns:
        return self._ratings(ctx)

    def read_eval(self, ctx: RuntimeContext):
        """k-fold split by element index modulo (CrossValidation.scala:26-67
        splitData semantics; queries ask for each test-fold user)."""
        p = self.params
        if p.eval_params is None:
            raise ValueError("eval requires DataSourceParams.eval_params")
        rc = self._ratings(ctx)
        k = p.eval_params.k_fold
        folds = []
        idx = np.arange(rc.n)
        for fold in range(k):
            test_sel = idx % k == fold
            train = RatingColumns(
                rc.user_ix[~test_sel], rc.item_ix[~test_sel],
                rc.rating[~test_sel], rc.t_millis[~test_sel],
                rc.users, rc.items)
            qa: List[Tuple[Query, ActualResult]] = []
            test_users = np.unique(rc.user_ix[test_sel])
            for u in test_users:
                sel = test_sel & (rc.user_ix == u)
                ratings = [(rc.items.inverse(int(i)), float(r))
                           for i, r in zip(rc.item_ix[sel], rc.rating[sel])]
                qa.append((Query(user=rc.users.inverse(int(u)),
                                 num=p.eval_params.query_num),
                           ActualResult(tuple(ratings))))
            folds.append((train, f"fold{fold}", qa))
        return folds


# -- algorithm --------------------------------------------------------------

@dataclass(frozen=True)
class ALSAlgorithmParams(Params):
    rank: int = 10
    num_iterations: int = 10
    lambda_: float = 0.01
    seed: Optional[int] = None


class ALSAlgorithm(Algorithm):
    params_class = ALSAlgorithmParams
    query_class = Query

    def train(self, ctx: RuntimeContext, pd: RatingColumns) -> als.ALSModel:
        p = self.params
        if pd.n == 0:
            raise ValueError(
                "No rating events found; check appName and event import "
                "(parity: ALSAlgorithm.scala:56-61 require non-empty)")
        # timings= feeds solver phases + solver_residual into the phase
        # report, arming the bench's convergence gate
        x, y = als.als_train(
            pd, rank=p.rank, iterations=p.num_iterations, reg=p.lambda_,
            seed=p.seed if p.seed is not None else 0, mesh=ctx.mesh,
            timings=ctx.phase_timings)
        return als.ALSModel(x, y, pd.users, pd.items)

    def predict(self, model: als.ALSModel, query: Query) -> PredictedResult:
        return self.batch_predict(model, [(0, query)])[0][1]

    def warm_serving(self, model: als.ALSModel, buckets,
                     mesh=None) -> int:
        """Deploy warmup: pin item factors device-resident and AOT-compile
        the per-bucket banned-index executables (blackList queries are the
        common case; whiteList queries use the dense-mask path). With a
        configured serving mesh — or a catalog past one device's capacity
        — the plan shards the factors row-wise across the mesh
        (`ShardedBucketedTopK`)."""
        from predictionio_tpu.ops.topk_sharded import serve_plan
        self._serve_plan = serve_plan(
            model.item_factors, k=Query(user="").num, buckets=buckets,
            banned_width=64, mesh=mesh)
        return self._serve_plan.warm()

    def fold_in(self, model: als.ALSModel, delta, fctx) -> als.ALSModel:
        """Streaming fold-in: closed-form ALS half-steps over the
        delta's touched rows only — touched users re-solved against
        fixed item factors, then touched items against the updated user
        factors. Untouched rows stay bit-identical; the periodic full
        retrain remains ground truth (streaming/updaters.py)."""
        from predictionio_tpu.streaming.updaters import (
            fold_als_items, fold_als_users,
        )
        p = self.params
        buy_rating = float(fctx.ds_params.get("buy_rating", 4.0))
        # touched sets under THIS template's event spec — the generic
        # change scan covers every event type, and a user touched only
        # by a foreign event has an empty rating history (folding that
        # would zero a perfectly good row)
        rated = fctx.delta_columns(
            entity_type="user", event_names=["rate", "buy"],
            value_spec={"*": 1.0}, require_target=True)
        if rated.n == 0:
            return None

        def value_of(ev):
            if ev.event == "buy":
                return buy_rating
            return ev.properties.get_or_else("rating", None)

        uf, users2, _ = fold_als_users(
            fctx, model.users, model.items, model.user_factors,
            model.item_factors, list(rated.entities),
            event_names=["rate", "buy"], value_of=value_of,
            dedup_last_wins=True, reg=p.lambda_)
        yf, _ = fold_als_items(
            fctx, users2, model.items, uf, model.item_factors,
            list(rated.targets), event_names=["rate", "buy"],
            value_of=value_of, dedup_last_wins=True, reg=p.lambda_)
        return als.ALSModel(uf, yf, users2, model.items)

    def batch_predict(self, model: als.ALSModel,
                      queries: Sequence[Tuple[int, Query]]
                      ) -> List[Tuple[int, PredictedResult]]:
        """One jit'd matmul+top_k over the whole batch; unknown users get
        empty results (ALSAlgorithm.scala:96-112 semantics). `lookup`
        (obs/trace.stage) here is the user id map and the user-factor
        gather; the rest of the cycle's stages are `score_and_rank`'s."""
        with trace.stage("lookup"):
            known = [(i, q, model.users.get(q.user)) for i, q in queries]
            out: List[Tuple[int, PredictedResult]] = [
                (i, PredictedResult()) for i, _, u in known if u is None]
            live = [(i, q, u) for i, q, u in known if u is not None]
            if not live:
                return out
            vecs = model.user_factors[np.array([u for _, _, u in live])]
        return out + score_and_rank(
            vecs, model.item_factors, model.items, live,
            plan=getattr(self, "_serve_plan", None))


# -- evaluation metrics (Evaluation.scala of the template) ------------------

class PrecisionAtK(OptionAverageMetric):
    """Precision@K with a rating threshold: of the top-K recommended
    items, the fraction the user actually rated >= threshold; None (skip)
    when the user has no positively-rated items in the test fold
    (`examples/scala-parallel-recommendation/blacklist-items/src/main/scala/
    Evaluation.scala`)."""

    def __init__(self, k: int = 10, rating_threshold: float = 2.0):
        self.k = k
        self.rating_threshold = rating_threshold

    def header(self) -> str:
        return f"Precision@K (k={self.k}, threshold={self.rating_threshold})"

    def calculate_one(self, q: Query, p: PredictedResult,
                      a: ActualResult) -> Optional[float]:
        positives = {item for item, r in a.ratings
                     if r >= self.rating_threshold}
        if not positives:
            return None
        top = [s.item for s in p.itemScores[:self.k]]
        if not top:
            return 0.0
        hits = sum(1 for item in top if item in positives)
        # Denominator is min(k, |positives|) as in the reference metric —
        # NOT the number of returned recommendations.
        return hits / min(self.k, len(positives))


# -- engine -----------------------------------------------------------------

class RecommendationEngine(EngineFactory):
    @classmethod
    def apply(cls) -> Engine:
        return Engine(
            data_source=RecommendationDataSource,
            preparator=IdentityPreparator,
            algorithms={"als": ALSAlgorithm, "": ALSAlgorithm},
            serving=FirstServing,
        )


def engine() -> Engine:
    return RecommendationEngine.apply()


register_engine("recommendation", RecommendationEngine)
