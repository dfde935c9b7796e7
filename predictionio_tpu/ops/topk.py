"""Masked top-k scoring — the serve-time hot path of every recommender.

The reference serves queries one at a time and even notes "TODO:
Parallelize" (`core/.../workflow/CreateServer.scala:494`); its per-query
work is a driver-side loop over `recommendProducts`
(`examples/.../ALSAlgorithm.scala:96-112`). Here scoring is one
program: a query batch of user vectors against the full item factor
matrix (a matmul), banned-index or dense-mask filters, then top-k — so
batching queries is free.

Who owns what:

  - **The serving plan, `BucketedPlan`**: the k clamp and the pow2
    bucket grid, chunking past the largest bucket, the idempotent
    `warm` loop, the call cycle (`pack` -> `launch` -> `fetch` stages,
    the merge-share observation, `_record_dispatch`, the slice back to
    the batch), `swap_factors`, `fits`. Written once, here. Calls go
    straight to AOT-compiled executables over a device-resident factor
    matrix (never the jit tracing cache), so steady-state serving is
    zero-recompile by construction.
  - **Its four leaves** supply how the factors are placed, one bucket's
    executable, the filter block a call carries and the dispatch label,
    and nothing else: `BucketedTopK` (ban lists; fused kernel or XLA
    chain) and `BucketedSimilar` (dense mask, cosine) here, on one
    device; `ShardedBucketedTopK` / `ShardedBucketedSimilar` in
    `ops/topk_sharded.py`, the same two with the catalog row-sharded
    over a mesh (per-shard partial top-k + allgather merge). That
    module also chooses among them (`serve_plan` / `similar_plan`).
    `TieredTopK` and `ShardSliceTopK` wrap a plan and translate ids
    around it; they hold no copy of the cycle.
  - **`score_banned` / `score_similar`**: which rows of a template's
    batch go through its plan, and which through the generic entry
    points. The templates call these and decide nothing.
  - **The generic entry points** `topk_scores` / `topk_similar` /
    `topk_scores_filtered` route by score-matrix size. Small problems
    (a handful of live queries against a catalog of thousands) run as
    host BLAS in microseconds — pushing them through the accelerator
    costs a dispatch + a device->host readback round trip that dwarfs
    the compute. Large ones (offline batchpredict, eval sweeps, big
    catalogs) go to the jit'd chain where the MXU matmul wins and the
    transfer amortizes. Inside a jit trace the device path is always
    used (host numpy cannot trace). `DispatchPolicy` refines the static
    size rule: it keeps a latency EWMA per path and can PROMOTE
    sub-crossover problems to the device once the observed device round
    trip beats the predicted (GIL-contended) host time; the static
    `HOST_CROSSOVER_CELLS` stays the upper bound.
  - **The XLA scoring chain** (`exact_scores`, `masked_topk`,
    `drop_banned`, `unit_rows`): shared by the jitted generic paths,
    the plans' chain buckets and the shard-local bodies.

Every dispatch lands in `pio_topk_dispatch_total{path=host|device|
fused|sharded}` (the process-default metrics registry) and in
`DISPATCH_COUNTS`.
"""

from __future__ import annotations

import threading
import time
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.obs import trace

NEG_INF = -1e30

# [b, n_items] score cells below which the host path wins. Environment-
# dependent (host BLAS speed x device dispatch overhead); the bench
# reports it as serve_topk_crossover_cells_measured. The default is not
# measured on a local chip; operators can pin a measured value via
# PIO_TOPK_HOST_CROSSOVER_CELLS.
import os as _os

HOST_CROSSOVER_CELLS = int(_os.environ.get(
    "PIO_TOPK_HOST_CROSSOVER_CELLS", 4 << 20))

# Dispatch evidence: incremented per call by which path actually served
# it (the traced/jit path counts as "device" — it compiles into a device
# program). Read by the bench to PROVE the device path ran, and by tests;
# plain ints under the GIL (worst case a lost increment, never a wrong
# path).
DISPATCH_COUNTS = {"host": 0, "device": 0, "sharded": 0, "fused": 0}

# Below this many score cells the amortized policy never promotes to the
# device, whatever the EWMAs say: tiny unit-test-sized problems must stay
# deterministically on the host path (and the promotion payoff only
# exists for coalesced serve batches anyway).
PROMOTE_FLOOR_CELLS = int(_os.environ.get(
    "PIO_TOPK_PROMOTE_FLOOR_CELLS", 1 << 16))

# Exploration cadence for the amortized policy: with no device
# observation yet, every Nth promotable-sized problem is routed to the
# device purely to SEED its latency EWMA. Without this the policy can
# never promote (promotion needs a device EWMA, but sub-crossover
# problems all go to the host, so the device EWMA is never observed —
# the r05 ecommerce runs served 552 host calls and 0 device batches
# exactly this way). 0 disables probing.
EXPLORE_EVERY = int(_os.environ.get("PIO_TOPK_EXPLORE_EVERY", 32))

_DISPATCH_TOTAL = None


def _dispatch_total():
    """`pio_topk_dispatch_total{path=...}` in the process-default
    registry (lazy: created on the first dispatch, like jaxprobe's
    counters)."""
    global _DISPATCH_TOTAL
    if _DISPATCH_TOTAL is None:
        from predictionio_tpu.obs import get_registry
        _DISPATCH_TOTAL = get_registry().counter(
            "pio_topk_dispatch_total",
            "Top-k serve dispatches by path taken (host BLAS, "
            "single-device program, or mesh-sharded program; traced "
            "calls count as device)", labels=("path",))
    return _DISPATCH_TOTAL


_MERGE_SHARE = None


def _observe_merge_share(merged, blocks: int) -> None:
    """One fused call's `merged / blocks` into `pio_topk_merge_share`
    (process-default registry, lazy like `_dispatch_total`): the share
    of the kernel's gate sub-blocks whose merge ran
    (ops/fused_topk.py)."""
    global _MERGE_SHARE
    try:
        if _MERGE_SHARE is None:
            from predictionio_tpu.obs import get_registry
            _MERGE_SHARE = get_registry().histogram(
                "pio_topk_merge_share",
                "Share of the fused top-k kernel's gate sub-blocks that "
                "held a score above a row's k-th best and were merged, "
                "per call",
                buckets=(0.01, 0.02, 0.03, 0.05, 0.075, 0.1, 0.15, 0.2,
                         0.3, 0.5, 0.75, 1.0))
        _MERGE_SHARE.observe(merged / blocks)
    except Exception:
        pass  # metrics must never fail a serve call


def _publish_plan_temp_bytes(fused_exes) -> None:
    """Gauge `pio_serve_plan_temp_bytes` from a warmed plan's fused
    bucket executables: the largest one's temporaries by the compiler's
    own count (`memory_analysis().temp_size_in_bytes`), which the
    device's peak-bytes statistic does not see. A kernel handed its
    catalog in another layout than it lies in shows here as a
    catalog-sized temporary (ops/fused_topk.py). Metrics never fail a
    deploy: a backend that gives no analysis leaves the gauge absent."""
    try:
        sizes = [exe.memory_analysis().temp_size_in_bytes
                 for exe in fused_exes]
        if sizes:
            from predictionio_tpu.obs import get_registry
            get_registry().gauge(
                "pio_serve_plan_temp_bytes",
                "Temporaries of one fused top-k call, by the "
                "compiler's count (largest warmed bucket)").set(max(sizes))
    except Exception:
        pass


class DispatchPolicy:
    """Amortized host/device dispatch from observed per-path latency.

    Cold start reproduces the legacy one-shot rule exactly: device iff
    cells >= HOST_CROSSOVER_CELLS (read live, so tests and operators can
    pin it). Once BOTH paths have been observed, problems between
    PROMOTE_FLOOR_CELLS and the crossover are routed by predicted
    latency:

        host:   cells * host_s_per_cell_EWMA * (1 + in-flight host calls)
        device: device_call_s_EWMA   (dispatch + readback dominated at
                serve sizes; the matmul itself is microseconds)

    The (1 + in-flight) factor is the batch-coalescing term: concurrent
    host calls serialize on the GIL/BLAS while device dispatches overlap,
    so the more the micro-batcher (or the concurrent per-algorithm loop)
    piles onto the host path, the stronger the pull toward the device.
    Promotion is one-directional — at or above the static crossover the
    device always wins, as before — so a pinned
    PIO_TOPK_HOST_CROSSOVER_CELLS keeps its meaning as an upper bound.
    """

    def __init__(self, alpha: float = 0.25):
        self._alpha = alpha
        self._lock = threading.Lock()
        self._host_s_per_cell: Optional[float] = None
        self._device_call_s: Optional[float] = None
        # the mesh-sharded plan's per-call EWMA: observed so operators
        # (and the persisted snapshot) see all three paths' latency,
        # even though a warmed sharded plan is dispatched whenever the
        # batch fits it (mirroring the single-device plan)
        self._sharded_call_s: Optional[float] = None
        self._host_inflight = 0
        self._probe_tick = 0

    def choose(self, cells: int) -> str:
        if cells >= HOST_CROSSOVER_CELLS:
            return "device"
        if cells < PROMOTE_FLOOR_CELLS:
            # tiny problems are deterministically host — never probed
            return "host"
        with self._lock:
            h, d = self._host_s_per_cell, self._device_call_s
            inflight = self._host_inflight
            if d is None and EXPLORE_EVERY > 0:
                # no device observation yet: probe every Nth call so
                # the EWMA gets seeded and promotion becomes reachable
                self._probe_tick += 1
                if self._probe_tick % EXPLORE_EVERY == 0:
                    return "device"
        if h is None or d is None:
            return "host"
        return "device" if d <= cells * h * (1.0 + inflight) else "host"

    def host_begin(self) -> None:
        with self._lock:
            self._host_inflight += 1

    def host_end(self) -> None:
        with self._lock:
            self._host_inflight = max(0, self._host_inflight - 1)

    def observe(self, path: str, cells: int,
                seconds: Optional[float]) -> None:
        if seconds is None or cells <= 0:
            return
        a = self._alpha
        with self._lock:
            if path == "host":
                per_cell = seconds / cells
                prev = self._host_s_per_cell
                self._host_s_per_cell = (per_cell if prev is None
                                         else prev + a * (per_cell - prev))
            elif path == "sharded":
                prev = self._sharded_call_s
                self._sharded_call_s = (seconds if prev is None
                                        else prev + a * (seconds - prev))
            else:
                prev = self._device_call_s
                self._device_call_s = (seconds if prev is None
                                       else prev + a * (seconds - prev))

    def snapshot(self) -> dict:
        with self._lock:
            return {"host_s_per_cell": self._host_s_per_cell,
                    "device_call_s": self._device_call_s,
                    "sharded_call_s": self._sharded_call_s,
                    "host_inflight": self._host_inflight}

    def restore(self, state: dict) -> None:
        """Re-seed the EWMAs from a persisted `snapshot()` so a server
        restart/reload starts from the learned host/device crossover
        instead of the cold one-shot rule. The in-flight count is
        transient and never restored; junk fields are ignored."""
        with self._lock:
            h = state.get("host_s_per_cell")
            d = state.get("device_call_s")
            s = state.get("sharded_call_s")
            if isinstance(h, (int, float)) and h > 0:
                self._host_s_per_cell = float(h)   # lint: ok — host JSON
            if isinstance(d, (int, float)) and d > 0:
                self._device_call_s = float(d)     # lint: ok — host JSON
            if isinstance(s, (int, float)) and s > 0:
                self._sharded_call_s = float(s)    # lint: ok — host JSON


DISPATCH_POLICY = DispatchPolicy()


def _record_dispatch(path: str, cells: int,
                     seconds: Optional[float] = None,
                     bucket: int = 0) -> None:
    """Count one dispatch by the path that served it, feed the policy's
    EWMAs, and tell the batch cycle this thread is in (if it is in one:
    obs/trace.BatchTrace) which path it took and the rows it padded to
    — the drainer tags the cycle's member traces from there."""
    trace.note_dispatch(path, bucket)
    DISPATCH_COUNTS[path] += 1
    try:
        _dispatch_total().labels(path=path).inc()
    except Exception:
        pass  # metrics must never fail a serve call
    DISPATCH_POLICY.observe(path, cells, seconds)


def exact_scores(vecs, item_factors):
    """[b, rank] x [n, rank] -> [b, n] scores, the product every XLA
    scoring chain starts with (the shard-local bodies of
    ops/topk_sharded.py too)."""
    # HIGHEST precision: the host path computes exact f32, and the two
    # paths must rank near-tied scores identically (default TPU matmul
    # precision is bf16-pass and would reorder them)
    return jnp.matmul(vecs, item_factors.T,
                      precision=jax.lax.Precision.HIGHEST)


def unit_rows(x, xp=jnp):
    """Rows scaled to unit length, the cosine scorer's half of the
    chain (`xp=np` on the host path)."""
    return x / (xp.linalg.norm(x, axis=-1, keepdims=True) + 1e-9)


def masked_topk(vecs, item_factors, mask, k: int):
    """The dense-mask chain: scores, disallowed cells to NEG_INF,
    top-k."""
    return jax.lax.top_k(
        jnp.where(mask, exact_scores(vecs, item_factors), NEG_INF), k)


def drop_banned(scores, banned):
    """Scores with each row's banned columns at NEG_INF; out-of-range
    fill indices (== the column count) are dropped."""
    rows = jnp.arange(scores.shape[0])[:, None]
    return scores.at[rows, banned].set(NEG_INF, mode="drop")


def _jit_pair(raw, static: tuple):
    """`raw` jitted twice: plain, and donating the per-call uploads
    (the padded query block, arg 0, and its filter block, arg 2) so XLA
    reuses their buffers instead of allocating fresh ones every drain.
    The factor matrix (arg 1) is the resident model state and is never
    donated. Both keep `raw`'s name, which is the compiled module's
    name in a profile."""
    jit = partial(jax.jit, static_argnames=static)
    return jit(raw), jit(raw, donate_argnums=(0, 2))


def _plan_jit(plain, donated):
    """Which of a pair a serving plan compiles: CPU backends cannot
    donate and would warn per compile."""
    return plain if jax.default_backend() == "cpu" else donated


@partial(jax.jit, static_argnames=("k",))
def _topk_scores_device(user_vecs, item_factors, mask, *, k: int):
    return masked_topk(user_vecs, item_factors, mask, k)


def _topk_similar_raw(query_vecs, item_factors, mask, *, k: int):
    return masked_topk(unit_rows(query_vecs), unit_rows(item_factors),
                       mask, k)


_topk_similar_device, _topk_similar_donated = _jit_pair(
    _topk_similar_raw, ("k",))


def _is_traced(*arrays) -> bool:
    return any(isinstance(a, jax.core.Tracer) for a in arrays)


def _on_device(*arrays) -> bool:
    return any(isinstance(a, jax.Array) for a in arrays)


def _topk_host(scores: np.ndarray, k: int):
    """Full stable argsort (cheap at host-path sizes) so tie-breaking
    matches lax.top_k's lowest-index-first guarantee — the host and
    device paths must return identical results for the same query.

    Cross-path parity is exact only for bitwise-equal scores (e.g. the
    integer-valued factors in the parity tests): the host matmul is exact
    f32 BLAS while the device path is XLA Precision.HIGHEST, so near-tied
    (but not equal) scores can still rank differently at the last ulp.
    Indices are cast to int32 to match lax.top_k's return dtype."""
    k = min(k, scores.shape[1])
    ix = np.argsort(-scores, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(scores, ix, axis=1), ix.astype(np.int32)


# ---------------------------------------------------------------------------
# Device-resident model arrays and the banned-index device path.
#
# The serving hot loop calls topk with the SAME host factor matrix every
# time; without caching, each device dispatch re-uploads it (a 500k x 64
# catalog is 128 MB per call; the cost on a local chip is not measured).
# `device_resident` uploads once per (array identity) and returns the
# cached jax.Array.
# ---------------------------------------------------------------------------

_DEVICE_RESIDENT: dict = {}


def device_resident(arr):
    """Device-put `arr` once and cache by object identity (evicted when
    the host array is garbage-collected). jax arrays pass through."""
    import weakref

    if isinstance(arr, (jax.Array, jax.core.Tracer)):
        return arr
    key = id(arr)
    hit = _DEVICE_RESIDENT.get(key)
    if hit is not None and hit[0]() is arr:
        return hit[1]
    dev = jax.device_put(arr)
    ref = weakref.ref(arr, lambda _, key=key: _DEVICE_RESIDENT.pop(key, None))
    _DEVICE_RESIDENT[key] = (ref, dev)
    return dev


# Live serving plans with device-pinned factor state, weakly held: the
# capacity checks in ops/topk_sharded subtract these bytes (the
# pio_plan_resident_bytes the server samples) before deciding whether a
# NEW catalog still fits one device — without the subtraction,
# back-to-back /reloads of a near-capacity catalog pass the fits check
# against an EMPTY device and OOM once both plans are resident (the old
# deployment stays pinned until the atomic swap completes).
_RESIDENT_PLANS: "weakref.WeakSet" = None  # type: ignore[assignment]


def register_resident_plan(plan) -> None:
    """Track a plan whose factor state is device-resident. Weak
    references only: a dropped deployment's plan leaves the accounting
    as soon as it is garbage-collected."""
    import weakref
    global _RESIDENT_PLANS
    if _RESIDENT_PLANS is None:
        _RESIDENT_PLANS = weakref.WeakSet()
    _RESIDENT_PLANS.add(plan)


def plan_resident_bytes() -> float:
    """Per-device bytes currently pinned by live serving plans."""
    if _RESIDENT_PLANS is None:
        return 0.0
    total = 0.0
    for plan in list(_RESIDENT_PLANS):
        try:
            total += float(plan.resident_per_device_bytes())
        except Exception:   # noqa: BLE001 — accounting is best-effort
            continue
    return total


def _topk_scores_banned(user_vecs, item_factors, banned, *,
                        k: int, has_bans: bool):
    scores = exact_scores(user_vecs, item_factors)
    if has_bans:
        scores = drop_banned(scores, banned)
    return jax.lax.top_k(scores, k)


_topk_scores_banned_device, _topk_scores_banned_donated = _jit_pair(
    _topk_scores_banned, ("k", "has_bans"))


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length() if n > 1 else 1


def _ban_block(rows: int, width: int, n_items: int,
               banned_lists) -> np.ndarray:
    """[rows, width] int32 ban indexes, one list a row from the top,
    the rest `n_items`: the fill the scatter drops."""
    block = np.full((rows, width), n_items, np.int32)
    for row, bl in enumerate(banned_lists):
        if len(bl):
            block[row, :len(bl)] = np.asarray(bl, np.int32)  # lint: ok
    return block


def topk_scores_filtered(user_vecs, item_factors, banned_lists, *, k: int):
    """Top-k scoring with per-query banned-item index lists (blacklist /
    seen filtering) instead of a dense [b, n_items] mask.

    Host/device dispatch as `topk_scores`, but the device path builds the
    filter ON DEVICE from a small padded [b, max_banned] index array —
    uploading a dense bool mask per batch costs b*n_items bytes (32 MB at
    batch 64 x 500k items) per call, while the index form is a few KB.
    The factor matrix goes through `device_resident`. Batch and
    banned-width are padded to powers of two so the jit cache stays at
    O(log^2) variants instead of one per observed shape.

    Whitelists need the dense-mask form — use `topk_scores` for those.
    """
    n_items = item_factors.shape[0]
    k = min(k, n_items)
    b = user_vecs.shape[0]
    cells = b * n_items
    traced = _is_traced(user_vecs, item_factors)
    on_dev = _on_device(user_vecs, item_factors)
    max_banned = max((len(bl) for bl in banned_lists), default=0)
    wp = _next_pow2(max_banned) if max_banned else 0
    if not traced and not on_dev \
            and DISPATCH_POLICY.choose(cells) == "host":
        # small problems: densify the filter and delegate so the host
        # scoring/tie-breaking path exists in exactly one place
        mask = np.ones((b, n_items), bool)
        for row, banned in enumerate(banned_lists):
            if len(banned):
                mask[row, np.asarray(banned, int)] = False  # lint: ok
        return topk_scores(user_vecs, item_factors, mask, k=k)
    if traced or on_dev:
        # traced / already-on-device inputs: no host-side padding
        # round-trip; shapes are what the trace gives us
        _record_dispatch("device", cells)
        banned = _ban_block(b, max(wp, 1), n_items, banned_lists)
        out = _topk_scores_banned_device(
            user_vecs, item_factors, jnp.asarray(banned), k=k,
            has_bans=wp > 0)
        return out if traced else jax.device_get(out)
    # host inputs: pad batch to a power of two to bound jit variants
    t0 = time.perf_counter()
    with trace.stage("pack"):
        bp = _next_pow2(b)
        vecs = np.zeros((bp, user_vecs.shape[1]), np.float32)
        vecs[:b] = user_vecs
        banned = _ban_block(bp, max(wp, 1), n_items, banned_lists)
    with trace.stage("launch"):
        out = _topk_scores_banned_device(
            jnp.asarray(vecs), device_resident(item_factors),
            jnp.asarray(banned), k=k, has_bans=wp > 0)
    with trace.stage("fetch"):
        scores, ixs = jax.device_get(out)
    _record_dispatch("device", cells, time.perf_counter() - t0, bp)
    return scores[:b], ixs[:b]


def _topk_dense(device_fn, cosine: bool, vecs, item_factors, mask, k: int):
    """`topk_scores` and `topk_similar`: one dense-mask top-k, the
    scorer (`device_fn` on the device, rows normalised first on the
    host when `cosine`) its argument. Dispatches host/device by problem
    size (see module docstring)."""
    traced = _is_traced(vecs, item_factors, mask)
    k = min(k, item_factors.shape[0])   # both paths clamp identically
    cells = vecs.shape[0] * item_factors.shape[0]
    if traced:
        _record_dispatch("device", cells)
        return device_fn(vecs, item_factors, mask, k=k)
    if _on_device(vecs, item_factors) \
            or DISPATCH_POLICY.choose(cells) == "device":
        t0 = time.perf_counter()
        item_factors = device_resident(item_factors)
        out = jax.device_get(device_fn(vecs, item_factors, mask, k=k))
        _record_dispatch("device", cells, time.perf_counter() - t0)
        return out
    t0 = time.perf_counter()
    DISPATCH_POLICY.host_begin()
    try:
        q = np.asarray(vecs)            # lint: ok — host-path arrays
        f = np.asarray(item_factors)    # lint: ok — host-path arrays
        if cosine:
            q, f = unit_rows(q, np), unit_rows(f, np)
        scores = np.where(np.asarray(mask), q @ f.T,  # lint: ok — host mask
                          np.float32(NEG_INF))
        out = _topk_host(scores, k)
    finally:
        DISPATCH_POLICY.host_end()
    _record_dispatch("host", cells, time.perf_counter() - t0)
    return out


def topk_scores(user_vecs, item_factors, mask, *, k: int):
    """scores = U @ Y^T with invalid items masked out.

    user_vecs:    [b, rank]
    item_factors: [n_items, rank]
    mask:         [b, n_items] bool — True = item allowed for that query
    Returns (scores [b, k], indexes [b, k]); masked-out slots score NEG_INF.
    """
    return _topk_dense(_topk_scores_device, False, user_vecs,
                       item_factors, mask, k)


def topk_similar(query_vecs, item_factors, mask, *, k: int):
    """Cosine-similarity top-k: used by the similarproduct template
    (`examples/scala-parallel-similarproduct/.../ALSAlgorithm.scala`
    cosine scoring). query_vecs [b, rank] are typically item vectors."""
    return _topk_dense(_topk_similar_device, True, query_vecs,
                       item_factors, mask, k)


def build_mask(n_items: int,
               blacklist_ix: Sequence[int] = (),
               whitelist_ix: Optional[Sequence[int]] = None,
               batch: int = 1) -> np.ndarray:
    """Host-side mask assembly from index lists (unknown ids are resolved
    to indexes by the caller via BiMap and simply absent here)."""
    if whitelist_ix is not None:
        mask = np.zeros(n_items, bool)
        mask[np.asarray(list(whitelist_ix), int)] = True  # lint: ok
    else:
        mask = np.ones(n_items, bool)
    if len(blacklist_ix):
        mask[np.asarray(list(blacklist_ix), int)] = False  # lint: ok
    return np.broadcast_to(mask, (batch, n_items))


# ---------------------------------------------------------------------------
# The deploy-warmed serving plan: bucketed AOT executables.
# ---------------------------------------------------------------------------

# Batch buckets warmed by default (powers of two; the micro-batcher's
# batch_max caps which of these a deployment actually compiles).
DEFAULT_SERVE_BUCKETS = (1, 2, 4, 8, 16, 32, 64)


class BucketedPlan:
    """A deploy-warmed serving plan: top-k over a device-resident
    factor matrix through one AOT-compiled executable per batch bucket
    (module docstring: what is here, what a leaf supplies).

    Built once at deploy warmup (`Algorithm.warm_serving` via
    `CoreWorkflow.prepare_deploy`):

      - the factor matrix is placed ONCE and pinned for the plan's
        lifetime (no per-call re-transfer);
      - every bucket in `buckets` is `.lower(...).compile()`d up front
        with a FIXED filter width, so a serve call dispatches straight
        to a compiled executable — the jit tracing cache is never
        consulted and steady state is zero-recompile by construction
        (jaxprobe's `pio_jax_backend_compiles_total` stays flat across
        drains);
      - off-CPU, the padded query block and filter block are donated
        (their buffers are dead after the call by construction).

    A call pads the batch up to the smallest warmed bucket (padded
    lanes: zero vectors and an empty filter; they are sliced off before
    return and can never leak into results).
    """

    # what `_record_dispatch` is told of a call through the XLA chain
    # and through the fused kernel
    path = "device"
    fused_path = "fused"
    # ban lists longer than this do not fit; a dense-mask plan takes none
    banned_width = 0

    def __init__(self, item_factors, *, k: int,
                 buckets: Sequence[int] = DEFAULT_SERVE_BUCKETS):
        host = np.ascontiguousarray(item_factors, dtype=np.float32)
        self.n_items, self.rank = host.shape
        self.k = max(1, min(k, self.n_items))
        self.buckets = tuple(sorted({_next_pow2(b)
                                     for b in buckets if b > 0})) or (1,)
        self._exe: dict = {}
        # bucket sizes served by the single-launch fused kernel
        # (ops/fused_topk.py; the rest keep the XLA chain), and the
        # sub-blocks its gate judges in one call
        self._fused_sizes: set = set()
        self._gate_blocks = 0
        # the host alias is the rollback token of the next swap (and
        # keeps `device_resident`'s weakref cache entry alive)
        self._host_factors = host
        self.factors = self._place(host)
        register_resident_plan(self)

    # -- what a leaf supplies ------------------------------------------------
    def _place(self, host: np.ndarray):
        """`host` on the device(s), as the bucket executables take it.
        Here: whole, on one device, through the identity-keyed
        residency cache the generic paths share."""
        return device_resident(host)

    def resident_per_device_bytes(self) -> float:
        """Bytes this plan pins on ONE device (here the whole block)."""
        return float(self._host_factors.nbytes)

    def _compile_bucket(self, bucket: int):
        """The compiled `(vecs, factors, filter) -> (scores, ids[,
        merged])` executable of one bucket; a fused one goes through
        `_mark_fused`."""
        raise NotImplementedError

    def _filter_spec(self, bucket: int) -> jax.ShapeDtypeStruct:
        raise NotImplementedError

    def _filter_block(self, bucket: int, filt) -> np.ndarray:
        """One call's filter, padded to the bucket's fixed shape."""
        raise NotImplementedError

    # -- the shared machinery ------------------------------------------------
    def _lower(self, fn, bucket: int, **static):
        return fn.lower(
            jax.ShapeDtypeStruct((bucket, self.rank), np.float32),
            self.factors, self._filter_spec(bucket), **static).compile()

    def _mark_fused(self, bucket: int, gate_blocks: int) -> None:
        self._fused_sizes.add(bucket)
        self._gate_blocks = gate_blocks

    @property
    def fused_buckets(self) -> int:
        return len(self._fused_sizes)

    def warm(self) -> int:
        """AOT-lower/compile every bucket executable; returns how many
        were compiled (idempotent: already-warm buckets are skipped)."""
        compiled = 0
        for b in self.buckets:
            if b not in self._exe:
                self._exe[b] = self._compile_bucket(b)
                compiled += 1
        if compiled:
            _publish_plan_temp_bytes(self._exe[b]
                                     for b in self._fused_sizes)
        return compiled

    def bucket_kernels(self) -> dict:
        """Which kernel serves each warmed bucket: "fused" | "xla"."""
        return {b: "fused" if b in self._fused_sizes else "xla"
                for b in sorted(self._exe)}

    def swap_factors(self, item_factors) -> np.ndarray:
        """Hot-swap the resident factor block (the streaming refresher's
        commit). The bucket executables take the factor operand
        POSITIONALLY per call, so a same-shape/dtype replacement reuses
        every AOT executable — only the new block crosses host->device,
        zero recompiles. Returns the PREVIOUS host factors (the
        rollback token). Shape changes must re-warm instead."""
        host = np.ascontiguousarray(item_factors, dtype=np.float32)
        if host.shape != (self.n_items, self.rank):
            raise ValueError(
                f"swap_factors shape {host.shape} != "
                f"{(self.n_items, self.rank)}: catalog changed — a hot "
                "swap cannot resize the AOT plan; re-warm instead")
        factors = self._place(host)   # a failed placement swaps nothing
        prev, self._host_factors, self.factors = (
            self._host_factors, host, factors)
        return prev

    @property
    def max_bucket(self) -> int:
        return self.buckets[-1]

    def fits(self, *, k: int, max_banned: int = 0) -> bool:
        """Whether a batch with these parameters can use the plan."""
        return (bool(self._exe)
                and k <= self.k and max_banned <= self.banned_width)

    def _bucket_for(self, b: int) -> int:
        for bucket in self.buckets:
            if bucket >= b:
                return bucket
        return self.max_bucket

    def __call__(self, vecs, filt):
        """Score `vecs` [b, rank] against the resident factors under the
        leaf's filter (`filt`: one entry a row); returns host (scores
        [b, k], indexes [b, k]). Pads to the bucket grid; chunks past
        the biggest bucket."""
        vecs = np.asarray(vecs, np.float32)  # lint: ok — host in
        b = vecs.shape[0]
        top = self.max_bucket
        if b > top:
            parts = [self(vecs[lo:lo + top], filt[lo:lo + top])
                     for lo in range(0, b, top)]
            return (np.concatenate([p[0] for p in parts]),
                    np.concatenate([p[1] for p in parts]))
        bucket = self._bucket_for(b)
        exe = self._exe.get(bucket)
        if exe is None:
            raise RuntimeError(
                f"{type(self).__name__} bucket {bucket} not warmed; "
                "call warm() at deploy time")
        t0 = time.perf_counter()
        with trace.stage("pack"):
            block = np.zeros((bucket, self.rank), np.float32)
            block[:b] = vecs
            filt = self._filter_block(bucket, filt)
        with trace.stage("launch"):
            out = exe(block, self.factors, filt)
        with trace.stage("fetch"):
            # a fused bucket also returns how many sub-blocks it merged
            scores, ixs, *merged = jax.device_get(out)
        if merged:
            _observe_merge_share(merged[0], self._gate_blocks)
        _record_dispatch(
            self.fused_path if bucket in self._fused_sizes else self.path,
            bucket * self.n_items, time.perf_counter() - t0, bucket)
        return scores[:b], ixs[:b]


class BucketedTopK(BucketedPlan):
    """The ban-list plan on one device. Its filter block is the rows'
    banned item indexes at a FIXED width, filled with `n_items`, which
    the scatter drops. Queries with more bans than `banned_width`, a k
    above `self.k`, or whitelists / category filters that need a dense
    mask do not fit (`score_banned`)."""

    def __init__(self, item_factors, *, k: int,
                 buckets: Sequence[int] = DEFAULT_SERVE_BUCKETS,
                 banned_width: int = 256):
        self.banned_width = _next_pow2(max(1, banned_width))
        super().__init__(item_factors, k=k, buckets=buckets)

    def _compile_bucket(self, bucket: int):
        """With fusion on (`ops/fused_topk.py`, PIO_SERVE_FUSED gate)
        every bucket compiles the single-launch fused kernel, and a
        kernel that does not compile fails the warm-up; otherwise every
        bucket compiles the AOT XLA chain. Both have the same
        `(vecs, factors, banned)` signature, so `swap_factors` and the
        zero-recompile contract hold either way."""
        from predictionio_tpu.ops import fused_topk
        exe = fused_topk.maybe_build_bucket(
            self.factors, n_items=self.n_items, rank=self.rank,
            k=self.k, bucket=bucket, banned_width=self.banned_width)
        if exe is None:
            return self._lower(
                _plan_jit(_topk_scores_banned_device,
                          _topk_scores_banned_donated),
                bucket, k=self.k, has_bans=True)
        self._mark_fused(bucket, fused_topk.gate_blocks(
            self.n_items, self.k, self.rank))
        return exe

    def _filter_spec(self, bucket: int) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct((bucket, self.banned_width), np.int32)

    def _filter_block(self, bucket: int, banned_lists) -> np.ndarray:
        return _ban_block(bucket, self.banned_width, self.n_items,
                          banned_lists)


class BucketedSimilar(BucketedPlan):
    """The dense-mask cosine plan on one device (the similar-product
    template's `batch_predict`). Its filter block is the template's
    [b, n_items] category/white/black mask, padded with all-False rows
    (their lanes score NEG_INF and are sliced off before return) and,
    where the resident factors are padded (a mesh), all-False
    columns."""

    def _compile_bucket(self, bucket: int):
        return self._lower(
            _plan_jit(_topk_similar_device, _topk_similar_donated),
            bucket, k=self.k)

    def _filter_spec(self, bucket: int) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct((bucket, self.factors.shape[0]),
                                    np.bool_)

    def _filter_block(self, bucket: int, mask) -> np.ndarray:
        block = np.zeros((bucket, self.factors.shape[0]), bool)
        block[:len(mask), :self.n_items] = mask
        return block


def _by_fit(plan, generic, vecs, filt, ks, fit):
    """The slow road of `score_banned` / `score_similar`: some row does
    not fit the plan. Rows that do go through it, the rest through
    `generic(vecs, filt, k)`, and the results go back in the batch's
    order, `max(ks)` wide (NEG_INF where a route returned fewer)."""
    width = max(ks)
    if not any(fit):
        return generic(vecs, filt, width)
    vecs = np.asarray(vecs)  # lint: ok — host in
    scores = np.full((len(ks), width), NEG_INF, np.float32)
    ixs = np.zeros((len(ks), width), np.int32)
    on = [r for r, f in enumerate(fit) if f]
    off = [r for r, f in enumerate(fit) if not f]
    for rows, call in ((on, lambda v, f, _k: plan(v, f)), (off, generic)):
        s, i = call(vecs[rows], [filt[r] for r in rows],
                    max(ks[r] for r in rows))
        w = min(width, s.shape[1])
        scores[rows, :w], ixs[rows, :w] = s[:, :w], i[:, :w]
    return scores, ixs


def score_banned(plan, user_vecs, item_factors, banned_lists,
                 ks: Sequence[int]):
    """Ban-list top-k for a template's batch: through `plan` (a warmed
    ban-list plan, or None) where a row fits it (its own k, `ks[row]`,
    and its ban count), else through `topk_scores_filtered`. Decided
    row by row: one heavy user whose seen-history ban list overflows
    the plan's banned_width must not demote the whole coalesced batch
    to the generic (host-leaning) path — that all-or-nothing gate is
    how the r05 scale runs served hundreds of host calls and zero
    device batches. Returns host (scores, indexes), at least `max(ks)`
    wide."""
    if plan is not None and plan.fits(
            k=max(ks), max_banned=max(map(len, banned_lists), default=0)):
        return plan(user_vecs, banned_lists)
    fit = [plan is not None and plan.fits(k=k, max_banned=len(bl))
           for k, bl in zip(ks, banned_lists)]
    return _by_fit(
        plan, lambda v, f, k: topk_scores_filtered(v, item_factors, f, k=k),
        user_vecs, banned_lists, ks, fit)


def score_similar(plan, query_vecs, item_factors, mask, ks: Sequence[int]):
    """`score_banned`'s dense-mask twin: cosine top-k through `plan` (a
    warmed `similar_plan`, or None) where a row's k fits it, else
    through `topk_similar`."""
    if plan is not None and plan.fits(k=max(ks)):
        return plan(query_vecs, mask)
    fit = [plan is not None and plan.fits(k=k) for k in ks]
    return _by_fit(
        plan, lambda v, m, k: topk_similar(
            v, item_factors, np.asarray(m), k=k),  # lint: ok — host mask
        query_vecs, mask, ks, fit)
