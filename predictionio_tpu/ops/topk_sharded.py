"""Mesh-sharded serving plans: partial top-k per shard + global merge.

A catalog bigger than one chip's HBM cannot be pinned by `BucketedTopK`,
and every model's train step already shards over the device mesh. This
module gives serving the sharded-scoring shape "Scalable ML Training
Infrastructure at Google" describes for ads scoring: partition the
embedding (factor) table row-wise, score locally, merge partial top-k.

`ShardedBucketedTopK` / `ShardedBucketedSimilar` are the two plans of
`ops/topk.py` with another placement and another bucket program, and
that is all this module writes of them: the bucket grid, the call
cycle, `swap_factors` and `fits` are `topk.BucketedPlan`'s, the ban
and mask blocks their single-device parents'. What differs:

  - item factors are padded to a multiple of the shard count and
    device_put ONCE with a row sharding over the serve mesh's "items"
    axis (`parallel.mesh.shard_put`), so each device holds an
    `n_items/n_shards` slice of the catalog for the plan's lifetime;
  - every batch bucket is AOT-lowered/compiled against that resident
    sharded array: inside the program each shard computes its local
    score block (one matmul at `Precision.HIGHEST`, identical math to
    the single-device path), applies banned-index filtering IN GLOBAL
    ID SPACE (banned ids arrive untranslated; each shard subtracts its
    row base, routes out-of-shard ids to an out-of-bounds slot, and the
    scatter drops them), masks its padding
    rows to NEG_INF and takes a LOCAL `lax.top_k`; the shards'
    `k_shard` candidates come out of the shard_map stacked over the
    mesh axis and `_jit_merged` merges the `k_shard * n_shards` of them
    with a final top-k over globally-offset ids;
  - the merge is bit-identical to the single-device oracle, ties
    included: candidates concatenate in shard-major order (= global id
    order for equal scores, since `lax.top_k` is lowest-index-first
    within a shard), so the final top-k's positional tie-break
    reproduces the full-matrix `lax.top_k` exactly. Survival argument:
    any item in the global top-k has fewer than k items above it
    globally, hence fewer within its own shard, hence it is inside the
    shard's top-`min(k, per_shard)` candidates.

Path selection (`serve_plan`/`similar_plan` + `serve_mesh_from_conf`):
sharding engages when a mesh is explicitly configured (a `mesh` key in
the engine-instance/server runtime_conf, or `PIO_SERVE_SHARD=on`) or
when — under the default `PIO_SERVE_SHARD=auto` — the factor matrix
exceeds a single device's capacity (`PIO_DEVICE_HBM_BYTES` override,
else the backend's reported bytes_limit; unknown capacity, e.g. host
CPU, never auto-shards). `PIO_SERVE_SHARD=off` disables entirely and
`PIO_SERVE_SHARDS` caps the shard count.

Every sharded dispatch counts under `path="sharded"`; plan
construction publishes `pio_serve_shards` and per-shard
`pio_serve_shard_bytes{shard=...}` HBM-residency gauges.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.ops import topk
from predictionio_tpu.ops.topk import (
    DEFAULT_SERVE_BUCKETS, NEG_INF, BucketedSimilar, BucketedTopK,
    drop_banned, exact_scores, masked_topk, unit_rows,
)
from predictionio_tpu.parallel.mesh import (  # noqa: F401 — re-export
    parse_fleet_mesh, shard_put,
)

# the serve mesh's single axis: catalog rows are partitioned over it
SHARD_AXIS = "items"


@dataclass(frozen=True)
class ServeMesh:
    """A serving mesh plus HOW it was chosen: `forced` means sharding
    was explicitly configured (runtime_conf mesh / PIO_SERVE_SHARD=on)
    and engages regardless of catalog size; un-forced meshes only shard
    catalogs that exceed one device's capacity."""
    mesh: "jax.sharding.Mesh"
    forced: bool = False

    @property
    def n_shards(self) -> int:
        return int(self.mesh.shape[SHARD_AXIS])  # lint: ok — host meta


@dataclass(frozen=True)
class ShardSlice:
    """A CROSS-HOST fleet shard assignment: this member owns one
    contiguous row-slice of the catalog (shard `index` of `n_shards`,
    same ceil-divided block partition the local sharded plans use).
    Flows through `serve_plan`'s mesh slot, so the deploy warm path
    builds a `ShardSliceTopK` instead of a whole-catalog plan."""
    n_shards: int
    index: int


def serve_mesh_from_conf(conf=None):
    """The deploy-time serving mesh: the "items" axis over the local
    devices, or None when sharded serving is off or pointless (< 2
    devices). `conf` is the merged engine-instance + server
    runtime_conf; a configured training mesh there forces the sharded
    path (training and serving agree on the device layout). A
    cross-host `items=N@fleet:i` mesh returns a `ShardSlice` instead —
    this member serves only its owned catalog rows and the fleet
    router merges across members."""
    conf_mesh = str((conf or {}).get("mesh", "") or "")
    fleet = parse_fleet_mesh(conf_mesh)
    if fleet is not None:
        n, idx = fleet
        if idx is not None:
            return ShardSlice(n_shards=n, index=idx)
        # router-level spec: not a local device layout — never forces
        # local sharding on the process that merges
        conf_mesh = ""
    mode = (os.environ.get("PIO_SERVE_SHARD", "auto") or "auto").lower()
    if mode in ("off", "0", "false"):
        return None
    from jax.sharding import Mesh
    devices = jax.devices()
    want = int(os.environ.get("PIO_SERVE_SHARDS", "0") or 0)  # lint: ok
    n = min(want, len(devices)) if want > 0 else len(devices)
    if n < 2:
        return None
    forced = mode in ("on", "1", "true") or bool(conf_mesh)
    return ServeMesh(Mesh(np.array(devices[:n]),  # lint: ok — host list
                          (SHARD_AXIS,)), forced)


def device_capacity_bytes() -> Optional[float]:
    """Per-device HBM capacity for the fits-one-device check:
    `PIO_DEVICE_HBM_BYTES` wins, else the backend's reported
    bytes_limit, else None (unknown — host CPU backends report
    nothing, and an unknown capacity never auto-shards)."""
    env = os.environ.get("PIO_DEVICE_HBM_BYTES", "").strip()
    if env:
        return float(env)   # lint: ok — host env knob
    try:
        stats = jax.devices()[0].memory_stats() or {}
        limit = stats.get("bytes_limit")
        return float(limit) if limit else None  # lint: ok — host stat
    except Exception:
        return None


def effective_device_capacity() -> Optional[float]:
    """The byte budget a NEW plan may still pin on one device: raw
    capacity with 20% headroom for score/workspace buffers, MINUS the
    bytes live plans already hold resident (the server's
    pio_plan_resident_bytes). Without the subtraction, back-to-back
    /reloads of a near-capacity catalog pass the fits check against an
    EMPTY device and OOM once old + new deployments are both pinned
    (the old plan stays resident until the atomic swap completes)."""
    cap = device_capacity_bytes()
    if cap is None:
        return None
    return cap * 0.8 - topk.plan_resident_bytes()


def _wants_shard(n_items: int, rank: int,
                 mesh: Optional[ServeMesh]) -> bool:
    """Whether `serve_plan` should build the sharded plan: a usable
    mesh AND (explicitly configured, or the factor matrix does not fit
    one device — `BucketedTopK.fits`-style capacity check, with 20%
    headroom and resident-plan bytes subtracted, see
    `effective_device_capacity`)."""
    if mesh is None or not isinstance(mesh, ServeMesh) \
            or mesh.n_shards < 2:
        return False
    if mesh.forced:
        return True
    cap = effective_device_capacity()
    if cap is None:
        return False
    return n_items * rank * 4 > cap


def _tier_hot_items(n_items: int, rank: int) -> Optional[int]:
    """Hot-slab size when tiered storage should engage, else None.
    `PIO_SERVE_TIER=on` always tiers; `auto` (default) tiers only when
    the factor matrix exceeds the effective device budget; `off`
    never. `PIO_TIER_HOT_FRAC` sizes the slab explicitly; unset, the
    slab fills the effective budget (quarter-catalog fallback when the
    budget is unknown but tiering is forced on)."""
    from predictionio_tpu.ops import topk_tiered
    mode = topk_tiered.tier_mode()
    if mode == "off":
        return None
    cap = effective_device_capacity()
    nbytes = n_items * rank * 4
    if mode == "auto" and (cap is None or nbytes <= cap):
        return None
    frac = topk_tiered.hot_frac()
    if frac is not None:
        hot = int(n_items * frac)
    elif cap is not None and cap > 0:
        hot = int(cap // (rank * 4))
    else:
        hot = n_items // 4
    return max(1, min(hot, n_items))


def serve_plan(item_factors, *, k: int,
               buckets: Sequence[int] = DEFAULT_SERVE_BUCKETS,
               banned_width: int = 256,
               mesh=None):
    """The banned-index serving plan for this deployment. Selection
    order: a cross-host `ShardSlice` builds the member-local slice plan
    (whose inner plan recurses through this selection — a giant shard
    slice tiers itself); a local mesh that warrants it shards
    (`_wants_shard`); a catalog past the effective device budget tiers
    (`_tier_hot_items` / PIO_SERVE_TIER); else the single-device
    `BucketedTopK`. All satisfy the same warm/fits/__call__ contract."""
    n_items, rank = np.asarray(item_factors).shape  # lint: ok — host meta
    if isinstance(mesh, ShardSlice):
        return ShardSliceTopK(item_factors, k=k, buckets=buckets,
                              banned_width=banned_width, slice_spec=mesh)
    if _wants_shard(n_items, rank, mesh):
        return ShardedBucketedTopK(item_factors, k=k, buckets=buckets,
                                   banned_width=banned_width,
                                   mesh=mesh.mesh)
    hot = _tier_hot_items(n_items, rank)
    if hot is not None:
        from predictionio_tpu.ops.topk_tiered import TieredTopK
        return TieredTopK(item_factors, k=k, buckets=buckets,
                          banned_width=banned_width, hot_items=hot)
    return BucketedTopK(item_factors, k=k, buckets=buckets,
                        banned_width=banned_width)


def similar_plan(item_factors, *, k: int,
                 buckets: Sequence[int] = DEFAULT_SERVE_BUCKETS,
                 mesh=None):
    """The dense-mask cosine serving plan: sharded or single-device by
    the same selection rule as `serve_plan`. A cross-host `ShardSlice`
    keeps the single-device plan over the FULL catalog (the dense-mask
    path has no slice variant); every member then returns identical
    similar-items candidates and the router merge deduplicates — exact,
    just not memory-partitioned."""
    n_items, rank = np.asarray(item_factors).shape  # lint: ok — host meta
    if not isinstance(mesh, ShardSlice) and _wants_shard(n_items, rank,
                                                         mesh):
        return ShardedBucketedSimilar(item_factors, k=k, buckets=buckets,
                                      mesh=mesh.mesh)
    return BucketedSimilar(item_factors, k=k, buckets=buckets)


def _publish_shard_gauges(n_shards: int, per_shard: int,
                          rank: int) -> None:
    """Shard-count + per-shard HBM residency gauges; metrics must never
    fail a deploy."""
    try:
        from predictionio_tpu.obs import get_registry
        reg = get_registry()
        reg.gauge("pio_serve_shards",
                  "Shard count of the current sharded serving plan "
                  "(0/absent = single-device)").set(
                      float(n_shards))  # lint: ok — host int
        g = reg.gauge("pio_serve_shard_bytes",
                      "Resident factor bytes pinned per shard by the "
                      "sharded serving plan", labels=("shard",))
        for s in range(n_shards):
            g.labels(shard=str(s)).set(float(per_shard * rank * 4))
    except Exception:
        pass


def _jit_merged(local_candidates, k: int, mesh):
    """Jit `local_candidates` (a shard_map whose shards each return
    their `[1, b, k_shard]` scores and GLOBAL ids, stacked over the
    mesh axis) followed by the global merge. The merge runs outside
    the shard_map on the stacked `[n_shards, b, k_shard]` arrays — the
    partitioner inserts the all-gather — so the result is replicated by
    construction and the varying-axis check stays on. Off-CPU the
    per-call query block and ban/mask block are donated, as the
    single-device plans do."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def fn(vecs, factors, filt):
        s_all, g_all, *counts = local_candidates(vecs, factors, filt)
        b = s_all.shape[1]
        # shard-major concatenation = global-id order for ties
        s_cat = jnp.swapaxes(s_all, 0, 1).reshape(b, -1)
        g_cat = jnp.swapaxes(g_all, 0, 1).reshape(b, -1)
        sv, si = jax.lax.top_k(s_cat, k)
        # a fused local stage also stacks each shard's merge count
        return (sv, jnp.take_along_axis(g_cat, si, axis=1),
                *(c.sum() for c in counts))

    donate = () if jax.default_backend() == "cpu" else (0, 2)
    return jax.jit(fn, out_shardings=NamedSharding(mesh, P()),
                   donate_argnums=donate)


class _OverMesh:
    """Placement over the serve mesh, for a plan of `ops/topk.py` named
    after it in the bases: the (zero-padded) factors row-sharded ONCE
    with `shard_put`, each device holding `per_shard` rows of the
    `n_pad`, and every dispatch labelled `sharded`. Same shape => same
    mesh/axis sharding, so a `swap_factors` keeps the bucket
    executables."""

    path = fused_path = "sharded"

    def __init__(self, item_factors, *, mesh=None, **plan_args):
        self.mesh = mesh
        self.n_shards = int(mesh.shape[SHARD_AXIS])  # lint: ok — host
        super().__init__(item_factors, **plan_args)
        _publish_shard_gauges(self.n_shards, self.per_shard, self.rank)

    def _place(self, host: np.ndarray):
        factors, _ = shard_put(host, self.mesh, SHARD_AXIS)
        self.n_pad = int(factors.shape[0])  # lint: ok — shape meta
        self.per_shard = self.n_pad // self.n_shards
        # per-shard candidate count: a shard can never contribute more
        # rows than it holds (k > per_shard clamps, the merge still
        # sees >= k real candidates overall)
        self.k_shard = min(self.k, self.per_shard)
        return factors

    def resident_per_device_bytes(self) -> float:
        """Bytes this plan pins per device: one padded shard's rows."""
        return float(self.per_shard * self.rank * 4)

    def _merged(self, body, filter_spec, n_out: int = 2, check=True):
        """`body` over the shards, then the global merge, as one jit."""
        from jax.sharding import PartitionSpec as P
        return _jit_merged(jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(P(), P(SHARD_AXIS, None), filter_spec),
            out_specs=(P(SHARD_AXIS),) * n_out,
            check_vma=check), self.k, self.mesh)


class ShardedBucketedTopK(_OverMesh, BucketedTopK):
    """Banned-index top-k over a row-sharded resident factor matrix:
    per-shard partial top-k on-device, allgather + merge to the global
    top-k (module docstring has the full program shape and the
    tie-parity argument). Drop-in for `BucketedTopK`: ban lists stay
    in GLOBAL id space."""

    def _build(self, bucket: int):
        """The jitted program of one bucket. With fusion on
        (PIO_SERVE_FUSED gate) the per-shard stage is the fused kernel,
        whose grid is specialised to the bucket, and a kernel that does
        not compile fails the warm-up; otherwise the XLA body."""
        from jax.sharding import PartitionSpec as P
        from predictionio_tpu.ops import fused_topk
        per, n_items, kk = self.per_shard, self.n_items, self.k_shard
        local = fused_topk.shard_local_candidates(
            per, self.rank, k=kk, bucket=bucket,
            banned_width=self.banned_width, axis=SHARD_AXIS)
        if local is not None:
            self._mark_fused(bucket, fused_topk.gate_blocks(
                per, kk, self.rank) * self.n_shards)

        # Not `topk`'s ban-list chain: a shard must translate the
        # GLOBAL ban ids to its own columns and bound its rows by
        # `n_items`, which the single-device body has no mesh position
        # to do. The product and the scatter are the shared ones.
        def body(vecs, factors_local, banned):
            # vecs [b, rank] + banned [b, W] replicated; factors_local
            # [per_shard, rank] is this shard's catalog slice
            base = jax.lax.axis_index(SHARD_AXIS) * per
            # Out-of-shard ids (and the n_items filler) must be routed
            # to an explicitly out-of-bounds slot BEFORE the scatter —
            # `.at[]` wraps negative indices NumPy-style even under
            # mode="drop", so a bare `banned - base` would make a
            # banned id g also ban g + per_shard on the next shard.
            loc = banned - base
            loc = jnp.where((loc >= 0) & (loc < per), loc, per)
            if local is not None:
                # single launch: matmul + ban-mask + local top-k fused;
                # the shard's valid-row bound is mesh-position-dependent
                # and rides in as a scalar operand
                nv = jnp.clip(n_items - base, 0,
                              per).astype(jnp.int32).reshape((1,))
                s, ix, merged = local(nv, vecs, factors_local, loc)
                return s[None], (ix + base)[None], merged[None]
            scores = drop_banned(exact_scores(vecs, factors_local), loc)
            gids = base + jnp.arange(per)
            scores = jnp.where(gids[None, :] < n_items, scores, NEG_INF)
            s, ix = jax.lax.top_k(scores, kk)
            return s[None], (ix + base)[None]

        # Pallas' HLO interpreter (the CPU parity tests' stand-in for
        # Mosaic) evaluates the kernel jaxpr with the varying-axis check
        # on and trips over its own invariant grid indices; the compiled
        # kernel is traced with the check off by pallas_call itself, so
        # only the interpreted form has to opt out
        check = local is None or not fused_topk.interpreted()
        return self._merged(body, P(), 2 if local is None else 3, check)

    def _compile_bucket(self, bucket: int):
        return self._lower(self._build(bucket), bucket)


class ShardedBucketedSimilar(_OverMesh, BucketedSimilar):
    """Dense-mask cosine top-k over a row-sharded resident factor
    matrix (the similar-product template's filter shape): the mask is
    column-sharded to match the catalog rows, each shard normalizes
    its own factor slice (row-local math, identical to the
    single-device program), partial top-k, allgather + merge. Drop-in
    for `BucketedSimilar`."""

    def _compile_bucket(self, bucket: int):
        from jax.sharding import PartitionSpec as P
        per, kk = self.per_shard, self.k_shard

        def body(query_vecs, factors_local, mask_local):
            # the single-device chain on this shard's rows: padding
            # rows arrive masked False (the filter block pads the mask
            # columns with False), so no gid test is needed here
            s, ix = masked_topk(unit_rows(query_vecs),
                                unit_rows(factors_local), mask_local, kk)
            return s[None], (ix + jax.lax.axis_index(SHARD_AXIS) * per)[None]

        return self._lower(self._merged(body, P(None, SHARD_AXIS)), bucket)


class ShardSliceTopK:
    """The cross-host MEMBER-side plan: this process owns one
    contiguous ceil-divided row block of the catalog and serves
    shard-local candidates in GLOBAL id space; the fleet router merges
    candidates across members (shard-major, (-score, global id)
    tie-break — bit-identical to the single-device oracle by the same
    survival argument as the local sharded merge).

    The inner plan over the slice recurses through `serve_plan` with no
    mesh, so a slice that still exceeds the member's device budget
    tiers itself (`TieredTopK`) — the composition the giant-catalog
    path needs. Banned ids arrive untranslated (global); out-of-slice
    ids are dropped host-side before the inner plan sees them, so a
    boundary-straddling ban can neither leak nor alias a neighbor."""

    def __init__(self, item_factors, *, k: int,
                 buckets: Sequence[int] = DEFAULT_SERVE_BUCKETS,
                 banned_width: int = 256, slice_spec: ShardSlice = None):
        full = np.ascontiguousarray(item_factors, dtype=np.float32)  # lint: ok — host copy
        n_total, rank = full.shape
        n = int(slice_spec.n_shards)
        idx = int(slice_spec.index)
        per = -(-n_total // n)        # ceil: same block partition as
        self.base = min(per * idx, n_total)   # the local sharded plans
        self._hi = min(self.base + per, n_total)
        if self._hi <= self.base:
            raise ValueError(
                f"fleet shard {idx}/{n} is empty for {n_total} items — "
                "lower the shard count")
        self.slice_spec = slice_spec
        self.n_items = n_total        # global catalog size
        self.rank = rank
        self.slice_items = self._hi - self.base
        self.k = max(1, min(k, n_total))
        self.banned_width = banned_width
        self._inner = serve_plan(full[self.base:self._hi], k=k,
                                 buckets=buckets,
                                 banned_width=banned_width, mesh=None)

    # -- plan contract (delegates) ------------------------------------------
    @property
    def factors(self):
        return self._inner.factors

    @property
    def buckets(self):
        return self._inner.buckets

    @property
    def max_bucket(self) -> int:
        return self._inner.max_bucket

    def resident_per_device_bytes(self) -> float:
        # the inner plan registered itself; avoid double-counting
        return 0.0

    def warm(self) -> int:
        return self._inner.warm()

    def bucket_kernels(self) -> dict:
        return self._inner.bucket_kernels()

    def fits(self, *, max_banned: int, k: int) -> bool:
        # k above the slice's own candidate count still FITS: the
        # member legitimately contributes min(k, slice_items)
        # candidates and the router merge fills from other shards — a
        # fallback to the generic full-catalog path here would leak
        # out-of-slice items and duplicate candidates across members
        return (k <= self.k and max_banned <= self.banned_width
                and self._inner.fits(
                    max_banned=max_banned,
                    k=min(k, getattr(self._inner, "k", k))))

    def swap_factors(self, item_factors) -> np.ndarray:
        """Hot swap: accepts the FULL new catalog (streaming refresher)
        or a slice-shaped block (rollback token replay)."""
        host = np.ascontiguousarray(item_factors, dtype=np.float32)  # lint: ok — host copy
        if host.shape == (self.n_items, self.rank):
            return self._inner.swap_factors(host[self.base:self._hi])
        return self._inner.swap_factors(host)

    def __call__(self, user_vecs, banned_lists: Sequence[Sequence[int]]):
        """Shard-local top-k in global id space: returns (scores
        [b, k_local], GLOBAL ids [b, k_local]) for this member's rows
        only."""
        local = []
        for bl in banned_lists:
            if len(bl):
                arr = np.asarray(bl, np.int64)  # lint: ok — host ids
                arr = arr[(arr >= self.base) & (arr < self._hi)]
                local.append((arr - self.base).tolist())
            else:
                local.append(())
        scores, ixs = self._inner(user_vecs, local)
        return scores, ixs + np.int32(self.base)
