"""Two-tower neural retrieval model.

A NEW capability beyond the reference (SURVEY.md §7 phase 7 / BASELINE.md
config 5): embedding towers for users and items trained with in-batch
sampled softmax on interaction events — the standard neural retrieval
architecture the reference's ALS templates graduate to.

TPU design: one jit'd train step (embedding lookups -> MLP towers ->
in-batch softmax loss -> adam update), batch dimension sharded over the
mesh "data" axis so gradients all-reduce over ICI; inference materializes
both towers' embeddings once and serves via the same masked top-k matmul
as every other recommender.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclass
class TwoTowerModel:
    user_emb: np.ndarray    # [n_users, dim] final tower outputs
    item_emb: np.ndarray    # [n_items, dim]
    # raw tower weights, kept so streaming fold-in can run a warm-start
    # mini-epoch from the converged state (None on artifacts trained
    # before the streaming subsystem existed — those fall back to a
    # full rebuild)
    params: Optional[dict] = None

    def sanity_check(self):
        assert np.isfinite(self.user_emb).all()
        assert np.isfinite(self.item_emb).all()


def _init_params(key, n_users: int, n_items: int, emb_dim: int,
                 hidden: int, out_dim: int):
    ks = jax.random.split(key, 6)
    scale = 1.0 / np.sqrt(emb_dim)

    def dense(k, fan_in, fan_out):
        return (jax.random.normal(k, (fan_in, fan_out), jnp.float32)
                / np.sqrt(fan_in))

    return {
        "user_table": jax.random.normal(
            ks[0], (n_users, emb_dim), jnp.float32) * scale,
        "item_table": jax.random.normal(
            ks[1], (n_items, emb_dim), jnp.float32) * scale,
        "user_w1": dense(ks[2], emb_dim, hidden),
        "user_w2": dense(ks[3], hidden, out_dim),
        "item_w1": dense(ks[4], emb_dim, hidden),
        "item_w2": dense(ks[5], hidden, out_dim),
    }


def _tower(table, w1, w2, ix):
    h = jax.nn.relu(table[ix] @ w1)
    out = h @ w2
    return out / (jnp.linalg.norm(out, axis=-1, keepdims=True) + 1e-8)


def _loss_fn(params, u_ix, i_ix, temperature):
    """In-batch sampled softmax: each (u, i) pair treats the other items
    in the batch as negatives."""
    u = _tower(params["user_table"], params["user_w1"], params["user_w2"],
               u_ix)
    v = _tower(params["item_table"], params["item_w1"], params["item_w2"],
               i_ix)
    logits = (u @ v.T) / temperature                  # [b, b]
    labels = jnp.arange(u_ix.shape[0])
    return -jnp.mean(jax.nn.log_softmax(logits, axis=1)[labels, labels])


def twotower_train(u_ix: np.ndarray, i_ix: np.ndarray, *,
                   n_users: int, n_items: int,
                   emb_dim: int = 32, hidden: int = 64, out_dim: int = 32,
                   batch_size: int = 1024, epochs: int = 10,
                   lr: float = 1e-2, temperature: float = 0.1,
                   seed: int = 0, mesh=None,
                   init_params: Optional[dict] = None) -> TwoTowerModel:
    """Train on interaction pairs; returns materialized tower embeddings.

    `init_params` resumes from a prior model's weights (the streaming
    warm-start mini-epoch); optimizer state starts fresh, so a single
    epoch from converged weights moves them only slightly.
    """
    import optax

    n = len(u_ix)
    if n == 0:
        raise ValueError("no interaction pairs")
    batch_size = min(batch_size, n)
    key = jax.random.PRNGKey(seed)
    if init_params is not None:
        params = {k: jnp.asarray(v) for k, v in init_params.items()}
    else:
        params = _init_params(key, n_users, n_items, emb_dim, hidden,
                              out_dim)
    if mesh is not None and "model" in mesh.axis_names:
        # tensor parallelism: embedding tables row-sharded over "model"
        # (vocab dim), tower MLPs Megatron-style (w1 col-, w2 row-sharded);
        # XLA inserts the gathers/reduces over ICI
        from jax.sharding import NamedSharding, PartitionSpec as P

        def put(name, arr):
            spec = {"user_table": P("model", None),
                    "item_table": P("model", None),
                    "user_w1": P(None, "model"),
                    "item_w1": P(None, "model"),
                    "user_w2": P("model", None),
                    "item_w2": P("model", None)}[name]
            return jax.device_put(arr, NamedSharding(mesh, spec))

        params = {k: put(k, v) for k, v in params.items()}
    tx = optax.adam(lr)
    opt_state = tx.init(params)

    def step(params, opt_state, ub, ib):
        loss, grads = jax.value_and_grad(_loss_fn)(params, ub, ib,
                                                   temperature)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    rng = np.random.RandomState(seed)
    steps_per_epoch = max(n // batch_size, 1)
    if mesh is not None:
        # sharded batches arrive via device_put per step (the epoch data
        # is resharded by the mesh's batch sharding); dispatch overhead
        # is irrelevant under the virtual test meshes
        from predictionio_tpu.parallel import batch_sharding
        sharding = batch_sharding(mesh)          # dim 0 over "data"
        data_size = int(mesh.shape.get("data", 1))
        step = jax.jit(step)
        for _ in range(epochs):
            order = rng.permutation(n)
            for s in range(steps_per_epoch):
                sel = order[s * batch_size:(s + 1) * batch_size]
                ub, ib = jnp.asarray(u_ix[sel]), jnp.asarray(i_ix[sel])
                if len(sel) % data_size == 0:
                    ub = jax.device_put(ub, sharding)
                    ib = jax.device_put(ib, sharding)
                params, opt_state, loss = step(params, opt_state, ub, ib)
    else:
        # single-device: ONE dispatch per epoch via lax.scan over the
        # pre-uploaded shuffled batches. A per-step dispatch pays the
        # host round trip hundreds of times per epoch (what that costs
        # on a local chip: not measured)
        @jax.jit
        def epoch(params, opt_state, ub_all, ib_all):
            def body(carry, batch):
                p, o = carry
                ub, ib = batch
                p, o, loss = step(p, o, ub, ib)
                return (p, o), loss
            (params, opt_state), losses = jax.lax.scan(
                body, (params, opt_state), (ub_all, ib_all))
            return params, opt_state, losses

        m = steps_per_epoch * batch_size
        for _ in range(epochs):
            order = rng.permutation(n)[:m]
            ub_all = jnp.asarray(
                u_ix[order].reshape(steps_per_epoch, batch_size))
            ib_all = jnp.asarray(
                i_ix[order].reshape(steps_per_epoch, batch_size))
            params, opt_state, _ = epoch(params, opt_state, ub_all, ib_all)

    # one jitted program per tower (eager op-by-op materialization
    # compiles a handful of micro-programs per call; observed to tickle
    # a flaky XLA-CPU compiler crash in long-lived test processes)
    tower = jax.jit(_tower)
    user_emb = tower(params["user_table"], params["user_w1"],
                     params["user_w2"], jnp.arange(n_users))
    item_emb = tower(params["item_table"], params["item_w1"],
                     params["item_w2"], jnp.arange(n_items))
    return TwoTowerModel(np.asarray(user_emb), np.asarray(item_emb),
                         params={k: np.asarray(v)
                                 for k, v in params.items()})
