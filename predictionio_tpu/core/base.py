"""DASE component contracts: DataSource, Preparator, Algorithm, Serving,
Evaluator.

Parity targets: `core/.../core/{BaseDataSource,BasePreparator,BaseAlgorithm,
BaseServing,BaseEvaluator}.scala` and the user-facing flavors in
`core/.../controller/`.

Design decision (TPU-first): the reference splits every component into
P(parallel)/L(local)/P2L flavors because Spark forces a distinction between
RDD-resident and driver-resident values. Single-controller JAX has no such
split — training data are host/device arrays owned by one Python process
and sharded over the mesh by annotation — so there is ONE flavor of each
component. What survives of the P/L distinction is the *persistence*
semantics, expressed per-algorithm (see `persist_model` and
`PersistentModel` in persistence.py):
  - persist_model=True  ≙ P2L/LAlgorithm (model auto-serialized; reference
    `P2LAlgorithm.makePersistentModel`)
  - persist_model=False ≙ PAlgorithm returning () (retrain on deploy;
    reference `Engine.prepareDeploy:211-233`)
  - implementing PersistentModel ≙ custom save/load (reference
    `controller/PersistentModel.scala:30-115`)

Every component is constructed with a single Params dataclass — the analog
of `Doer`'s reflective ctor-with-Params (`core/.../core/AbstractDoer.scala`).
"""

from __future__ import annotations

from typing import Any, Generic, List, Optional, Sequence, Tuple, Type, TypeVar

from predictionio_tpu.core.params import EmptyParams, Params
from predictionio_tpu.core.runtime import RuntimeContext

TD = TypeVar("TD")   # training data
EI = TypeVar("EI")   # evaluation info
PD = TypeVar("PD")   # prepared data
Q = TypeVar("Q")     # query
P = TypeVar("P")     # predicted result
A = TypeVar("A")     # actual result
M = TypeVar("M")     # model


class TrainingInterrupted(Exception):
    """Base for the stop-after-* control-flow interruptions
    (WorkflowUtils.scala:388-392)."""


class StopAfterReadInterruption(TrainingInterrupted):
    pass


class StopAfterPrepareInterruption(TrainingInterrupted):
    pass


class _Component:
    """Shared ctor: every DASE component takes one Params dataclass."""

    params_class: Type[Params] = EmptyParams

    def __init__(self, params: Optional[Params] = None):
        if params is None or (isinstance(params, EmptyParams)
                              and self.params_class is not EmptyParams):
            # an EmptyParams placeholder (EngineParams' default) means "use
            # this component's default params"
            params = self.params_class()
        self.params = params

    def __repr__(self):
        return f"{type(self).__name__}({self.params!r})"


class DataSource(_Component, Generic[TD, EI, Q, A]):
    """Reads training and evaluation data from the event store
    (BaseDataSource.scala:37-54; PDataSource/LDataSource collapse)."""

    def read_training(self, ctx: RuntimeContext) -> TD:
        raise NotImplementedError

    def read_eval(self, ctx: RuntimeContext
                  ) -> Sequence[Tuple[TD, EI, Sequence[Tuple[Q, A]]]]:
        """k folds of (trainingData, evalInfo, [(query, actual)])
        (readEval, BaseDataSource.scala:43)."""
        return []


class Preparator(_Component, Generic[TD, PD]):
    """TD -> PD (BasePreparator.scala:36)."""

    def prepare(self, ctx: RuntimeContext, td: TD) -> PD:
        raise NotImplementedError


class IdentityPreparator(Preparator):
    """PD = TD passthrough (controller/IdentityPreparator.scala:29-93)."""

    def prepare(self, ctx: RuntimeContext, td):
        return td


class Algorithm(_Component, Generic[PD, M, Q, P]):
    """Train a model; answer queries (BaseAlgorithm.scala:58-125).

    `query_class` plays the role of the reference's `queryClass` ClassTag
    (BaseAlgorithm.scala:104-113): the serving layer extracts incoming JSON
    into it via `extract_params`. None = raw dict passthrough.
    """

    query_class: Optional[type] = None
    persist_model: bool = True

    def train(self, ctx: RuntimeContext, pd: PD) -> M:
        raise NotImplementedError

    def predict(self, model: M, query: Q) -> P:
        raise NotImplementedError

    def batch_predict(self, model: M, queries: Sequence[Tuple[int, Q]]
                      ) -> List[Tuple[int, P]]:
        """Bulk inference for eval/batchpredict; default maps `predict`
        (P2LAlgorithm.batchPredict default, P2LAlgorithm.scala:26-45).
        Algorithms with device-batched inference override this to run one
        jit'd program over all queries."""
        return [(i, self.predict(model, q)) for i, q in queries]

    def warm_serving(self, model: M, buckets: Sequence[int],
                     mesh=None) -> int:
        """Deploy-time warmup hook: pin model state device-resident and
        AOT-compile the serve executables for the given batch-size
        `buckets`, so the first real request (and every one after) hits a
        precompiled static shape. `mesh` (a `topk_sharded.ServeMesh`, or
        None) is the candidate serving mesh: algorithms with sharding-
        capable plans pass it to `serve_plan`/`similar_plan`, which
        partition model state across the mesh when it is configured or
        the catalog exceeds one device's capacity. Overrides that predate
        the mesh parameter are still called (warm_deploy inspects the
        signature). Returns the number of executables compiled; the
        default is a no-op for host-only algorithms. Called by
        `CoreWorkflow.prepare_deploy` after models are loaded."""
        return 0

    def serve_plans(self) -> tuple:
        """The top-k plan(s) `warm_serving` built (ops/topk.py), for
        what looks at a deployment from outside: `/status`, the plan
        gauges, the pager, the streaming refresher. The default answers
        from `_serve_plan`, where the templates keep theirs."""
        plan = getattr(self, "_serve_plan", None)
        return () if plan is None else (plan,)


class Serving(_Component, Generic[Q, P]):
    """Query supplement + multi-algorithm result combination
    (BaseServing.scala:33-42, controller/LServing.scala)."""

    def supplement(self, query: Q) -> Q:
        return query

    def serve(self, query: Q, predictions: Sequence[P]) -> P:
        raise NotImplementedError


class FirstServing(Serving):
    """Serve the first algorithm's prediction (controller/LServing.scala
    LFirstServing)."""

    def serve(self, query, predictions):
        return predictions[0]


class Evaluator(_Component):
    """Scores the output of Engine.eval (BaseEvaluator.scala:37-48).
    Concrete implementation: MetricEvaluator in evaluation.py."""

    def evaluate(self, ctx: RuntimeContext, engine, engine_params_list,
                 eval_data_set) -> Any:
        raise NotImplementedError


def sanity_check(obj: Any) -> None:
    """Run an object's sanity_check hook if present (SanityCheck trait,
    `core/.../controller/SanityCheck.scala`; called from Engine.train,
    Engine.scala:652-690)."""
    hook = getattr(obj, "sanity_check", None)
    if callable(hook):
        hook()
