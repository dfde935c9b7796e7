"""The power-retention mixer and the `tiny-brumby` preset: every layer a
degree-2 power-retention mixer (4 query heads on 2 KV heads of 16,
q/k-normed and rotated, a gate a KV head, chunks of 16) and a dense
SwiGLU of 128; an untied head. The degree-2 embedding, the recurrence
and the chunked kernel against the quadratic form, and the whole stack
against the plain reference of the benchmark
(`benchmark/brumby_reference.py`, which imports nothing of the program)
on seeded weights."""

import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT / "benchmark") not in sys.path:
    sys.path.append(str(ROOT / "benchmark"))

import brumby_datagen                                      # noqa: E402
import brumby_reference as bref                            # noqa: E402

from predictionio_tpu.ops import backbone as bb            # noqa: E402
from predictionio_tpu.ops import retention                 # noqa: E402
from predictionio_tpu.ops.seqrec import (                  # noqa: E402
    PackedEncoder, SeqRecModel, build_sequences, seqrec_encode,
    seqrec_train,
)

SEED = 3_000_000_019
CONFIGS = ROOT / "benchmark" / "configs"
BDOC = json.loads((CONFIGS / "tiny-brumby.json").read_text())
BCFG = bb.config_from_json(BDOC, "tiny-brumby")
# 1 and 3 events; 15, 16 and 17 about a chunk; 40 and 48: three chunks
BLENGTHS = (40, 1, 17, 3, 16, 15, 48, 33)


def quadratic(q, k, v, log_g, first, eps):
    """The mixer's quadratic form in float64, numpy: w_ts = exp(G_t -
    G_s) (q_t . k_s)^2 over the s <= t of t's own history."""
    q, k, v, log_g = (np.asarray(x, np.float64) for x in (q, k, v, log_g))
    T, H, P = q.shape
    group = H // k.shape[1]
    seg = np.cumsum(first)
    G = np.zeros_like(log_g)
    for t in range(T):
        G[t] = log_g[t] + (G[t - 1] if t and not first[t] else 0.0)
    y = np.zeros((T, H, P))
    for t in range(T):
        s = np.flatnonzero((seg == seg[t]) & (np.arange(T) <= t))
        for i in range(H):
            j = i // group
            w = np.exp(G[t, j] - G[s, j]) * (k[s, j] @ q[t, i]) ** 2
            y[t, i] = w @ v[s, j] / (w.sum() + eps)
    return y


def assert_near_the_recurrence(got, want):
    """Against `scan_steps`, whose phi(q) . phi(k) sums P (P + 1) / 2
    float32 terms of both signs: where (q . k)^2 is small against
    |q|^2 |k|^2 and a history's weights are few (its first events), it
    loses digits that the quadratic form keeps, up to 6e-3 at the
    kernel tests' draws; elsewhere the two agree to float32."""
    err = np.abs(np.asarray(got, np.float64) - want)
    assert np.median(err) < 1e-6 and err.max() < 1e-2, (
        np.median(err), err.max())


def _inputs(T, H, Hkv, P, firsts, seed=0):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((T, H, P)).astype(np.float32) / P ** 0.25
    k = rng.standard_normal((T, Hkv, P)).astype(np.float32) / P ** 0.25
    v = rng.standard_normal((T, Hkv, P)).astype(np.float32)
    log_g = np.log(rng.uniform(0.7, 1.0, (T, Hkv))).astype(np.float32)
    first = np.zeros(T, bool)
    first[list(firsts)] = True
    return q, k, v, log_g, first


@pytest.mark.parametrize("P", [2, 16, 128])
def test_phi_is_the_symmetric_degree2_embedding(P):
    """phi(q) . phi(k) = (q . k)^2 in P (P + 1) / 2 dimensions; each
    cross term u_a u_b (a < b) stands once, times sqrt(2), and each
    square once, times 1."""
    rng = np.random.default_rng(P)
    q, k = rng.standard_normal((2, 7, P))
    fq, fk = (np.asarray(retention.phi(jnp.asarray(x, jnp.float32)))
              for x in (q, k))
    assert fq.shape == (7, P * (P + 1) // 2)
    np.testing.assert_allclose((fq * fk).sum(-1), (q * k).sum(-1) ** 2,
                               rtol=1e-4, atol=1e-4)
    # on u = e_a + 2 e_b the entries are 1 (u_a^2), 4 (u_b^2) and
    # 2 sqrt(2) (the cross term), and nothing else
    for a, b in ((0, 1), (0, P - 1), (P // 2 - 1, P - 1)):
        if a == b:
            continue
        u = np.zeros(P, np.float32)
        u[a], u[b] = 1.0, 2.0
        got = np.sort(np.asarray(retention.phi(jnp.asarray(u))))
        want = np.zeros_like(got)
        want[-3:] = np.sort([1.0, 4.0, 2.0 * math.sqrt(2.0)])
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("firsts", [(0,), (0, 7, 20, 21, 33)])
def test_the_recurrence_is_the_quadratic_form(firsts):
    q, k, v, log_g, first = _inputs(40, 4, 2, 16, firsts)
    y = retention.scan_steps(q, k, v, log_g, first, 1e-6)
    assert_near_the_recurrence(quadratic(q, k, v, log_g, first, 1e-6),
                               np.asarray(y))


# (tokens, chunk, first events): histories that start mid-chunk, span
# one to five chunks, and are shorter than a chunk; one history alone
LAYOUTS = [
    (64, 16, (0,)),
    (80, 16, (0, 3, 9, 10, 27, 62)),
    (96, 16, (0, 5)),                    # 91 events: into a sixth chunk
    (64, 8, (0, 8, 16, 17, 18, 40, 47, 56)),
    (128, 32, (0, 31, 32, 33, 100)),
]


@pytest.mark.parametrize("T,chunk,firsts", LAYOUTS)
@pytest.mark.parametrize("heads", [(4, 2), (10, 2)])
def test_the_kernel_is_the_recurrence_on_packed_axes(T, chunk, firsts,
                                                     heads):
    H, Hkv = heads
    P = 16
    q, k, v, log_g, first = _inputs(T, H, Hkv, P, firsts, seed=T + H)
    y = retention.power_retention(
        jnp.asarray(q.reshape(T, -1)), jnp.asarray(k.reshape(T, -1)),
        jnp.asarray(v.reshape(T, -1)), jnp.asarray(log_g),
        jnp.asarray(first), None, chunk, 1e-6)
    assert y.dtype == jnp.float32 and y.shape == (T, H * P)
    y = np.asarray(y).reshape(T, H, P)
    assert_near_the_recurrence(
        y, np.asarray(retention.scan_steps(q, k, v, log_g, first, 1e-6)))
    # the pairs inside a chunk are (q . k)^2 itself: to float32
    np.testing.assert_allclose(y, quadratic(q, k, v, log_g, first, 1e-6),
                               rtol=1e-5, atol=1e-5)


def test_the_kernel_skips_no_state_a_served_token_reads():
    """Padding after the live tokens (`live` False from token 50): the
    chunks it begins read no state and the live tokens are unchanged,
    and a chunk's reads hang only on its first token."""
    T, chunk = 80, 16
    q, k, v, log_g, first = _inputs(T, 4, 2, 16, (0, 20, 50))
    live = np.arange(T) < 50
    args = [jnp.asarray(x) for x in (q.reshape(T, -1), k.reshape(T, -1),
                                     v.reshape(T, -1), log_g, first)]
    full = retention.power_retention(*args, None, chunk, 1e-6)
    cut = retention.power_retention(*args, jnp.asarray(live), chunk, 1e-6)
    np.testing.assert_array_equal(np.asarray(cut)[:50],
                                  np.asarray(full)[:50])
    assert np.isfinite(np.asarray(cut)).all()


def test_bfloat16_operands_stay_near_float32():
    T, chunk = 96, 16
    q, k, v, log_g, first = _inputs(T, 4, 2, 16, (0, 5, 70))
    want = quadratic(q, k, v, log_g, first, 1e-6)
    got = retention.power_retention(
        *(jnp.asarray(x.reshape(T, -1), jnp.bfloat16) for x in (q, k, v)),
        jnp.asarray(log_g), jnp.asarray(first), None, chunk, 1e-6)
    assert got.dtype == jnp.bfloat16
    err = np.abs(np.asarray(got, np.float32).reshape(want.shape) - want)
    assert 1e-4 < np.median(err) < 3e-2


def test_a_gradient_through_the_kernel_is_the_recurrences():
    T, chunk = 48, 16
    q, k, v, log_g, first = _inputs(T, 4, 2, 16, (0, 30))
    w = np.random.default_rng(1).standard_normal((T, 64)).astype(np.float32)

    def loss_kernel(q_, k_, v_, g_):
        return (retention.power_retention(q_, k_, v_, g_, first, None,
                                          chunk, 1e-6) * w).sum()

    def loss_steps(q_, k_, v_, g_):
        return (retention.scan_steps(
            q_.reshape(T, 4, 16), k_.reshape(T, 2, 16),
            v_.reshape(T, 2, 16), g_, first, 1e-6).reshape(T, -1)
            * w).sum()

    args = (jnp.asarray(q.reshape(T, -1)), jnp.asarray(k.reshape(T, -1)),
            jnp.asarray(v.reshape(T, -1)), jnp.asarray(log_g))
    for a, b in zip(jax.grad(loss_kernel, argnums=(0, 1, 2, 3))(*args),
                    jax.grad(loss_steps, argnums=(0, 1, 2, 3))(*args)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_config_from_json_reads_the_brumby_file():
    doc = json.loads((CONFIGS / "brumby-14b-pp5-8l.json").read_text())
    cfg = bb.config_from_json(doc, "brumby")
    assert cfg.layers == (("retention", "ffn_dense"),) * 8
    assert (cfg.hidden, cfg.n_heads, cfg.kv_heads_full, cfg.qk_dim,
            cfg.v_dim, cfg.dense_width, cfg.vocab) == (
                5120, 40, 8, 128, 128, 17408, 151936)
    assert (cfg.rotary_dim, cfg.rope_theta_full, cfg.qk_norm, cfg.eps,
            cfg.act, cfg.tied) == (128, 1e6, True, 1e-6, "silu", False)
    assert (cfg.retention_chunk, cfg.retention_eps) == (128, 1e-6)
    assert bb.n_params(cfg) == 4_198_652_992
    shapes = bb.param_shapes(cfg)["l0"]
    assert shapes["retention"]["w_gate"] == (5120, 8)
    assert shapes["retention"]["b_gate"] == (8,)
    assert shapes["ffn"]["w_gate"] == (5120, 17408)


@pytest.mark.parametrize("extra,message", [
    ({"layer_types": ["conv"]}, "one layer pattern"),
    ({"hybrid_override_pattern": "M"}, "one layer pattern"),
    ({"assumed": {"max_history": 48}}, "retention_chunk"),
])
def test_config_from_json_refuses_what_it_cannot_read(extra, message):
    with pytest.raises(ValueError, match=message):
        bb.config_from_json(dict(BDOC, **extra))


@pytest.fixture(scope="module")
def bweights():
    return (brumby_datagen.program_params(BDOC, SEED, jnp.float32),
            brumby_datagen.reference_params(BDOC, SEED))


@pytest.fixture(scope="module")
def bencoder(bweights):
    model = SeqRecModel(params=bweights[0], n_items=BCFG.vocab,
                        backbone=bb.config_dict(BCFG))
    enc = PackedEncoder(model, rows=8)
    enc.warm()
    return enc


@pytest.fixture(scope="module")
def bhistories():
    rng = np.random.default_rng(SEED % 1000)
    return [rng.integers(0, BCFG.vocab, n).tolist() for n in BLENGTHS]


def _logits(params, vecs):
    return np.asarray(vecs) @ np.asarray(params["head"], np.float32).T


def test_the_seeded_gates_keep_a_long_memory(bweights):
    """At zero input each KV head's half-life is 64 to 4,096 tokens in
    the program's own draw, and in the benchmark's what the
    configuration says (2 to 128 at the toy's short histories)."""
    init = bb.init_params(jax.random.PRNGKey(0), BCFG)
    for b, (lo, hi) in ((init["l0"]["retention"]["b_gate"], (64, 4096)),
                        (bweights[0]["l1"]["retention"]["b_gate"],
                         BDOC["assumed"]["gate_half_life"])):
        g = jax.nn.sigmoid(jnp.asarray(b))
        half_life = np.asarray(-math.log(2.0) / jnp.log(g))
        assert ((0.999 * lo <= half_life) & (half_life <= 1.001 * hi)).all()


def test_brumby_packed_stack_matches_reference_one_history_at_a_time(
        bweights, bencoder, bhistories):
    pp, rp = bweights
    got = _logits(pp, bencoder(bhistories))
    for row, h in zip(got, bhistories):
        np.testing.assert_allclose(row, bref.forward(BDOC, rp, h),
                                   atol=1e-5)


def test_brumby_a_history_alone_and_packed_behind_others_agree(
        bencoder, bhistories):
    """The state's leak test: a history of 1 or 3 events stands right
    behind one of 40 or 17; without the cut it would read their state."""
    packed = bencoder(bhistories)
    for j, h in enumerate(bhistories):
        np.testing.assert_allclose(bencoder([h])[0], packed[j], atol=1e-5)


def test_brumby_padded_training_layout_agrees_with_the_packed_one(
        bweights, bencoder, bhistories):
    pp, _ = bweights
    S = BCFG.max_history
    seqs = np.full((len(bhistories), S), BCFG.vocab, np.int32)
    for r, h in enumerate(bhistories):
        seqs[r, S - len(h):] = h
    model = SeqRecModel(params=pp, n_items=BCFG.vocab,
                        backbone=bb.config_dict(BCFG))
    np.testing.assert_allclose(seqrec_encode(model, seqs),
                               bencoder(bhistories), atol=1e-5)


@pytest.mark.parametrize("fault", sorted(bref.FAULTS))
def test_brumby_reference_faults_move_the_logits(bweights, bhistories,
                                                 fault):
    _, rp = bweights
    h = bhistories[0]
    moved = np.abs(bref.forward(BDOC, rp, h, **{fault: True})
                   - bref.forward(BDOC, rp, h)).max()
    assert moved > 1e-2, fault


def test_the_packed_encoder_counts_the_chunks_that_read_a_carried_state(
        bencoder):
    """`pio_seq_retention_carry_chunk_share`, once a call, by the
    kernel's own rule: a chunk reads where its first token continues a
    history that is served."""
    from predictionio_tpu.obs import get_registry
    import readers
    child = get_registry().histogram(
        "pio_seq_retention_carry_chunk_share").labels()
    n0, s0 = child.count, child.sum
    # 20 + 30 + 14 events in a bucket of 64: firsts at 0, 20 and 50;
    # chunks 1, 2 and 3 begin inside a history
    bencoder([[1] * 20, [2] * 30, [3] * 14])
    assert (child.count - n0, child.sum - s0) == (1, 0.75)
    # 16 + 16 + 8 in a bucket of 64: every live chunk begins a history,
    # and the padding's chunk is no one's
    bencoder([[1] * 16, [2] * 16, [3] * 8])
    assert (child.count - n0, child.sum - s0) == (2, 0.75)
    metric = json.loads((ROOT / "benchmark" / "layer_metrics"
                         / "seq_retention_carry_chunk_pct.json").read_text())
    assert metric["reader"] == "readers.hist_mean"
    facts = {"hist": {"pio_seq_retention_carry_chunk_share": {
        "count": 2, "sum": 0.75}}}
    assert readers.hist_mean(facts, **metric["args"]) == 37.5
    assert readers.hist_mean({"hist": {}}, **metric["args"]) is None


def test_one_executable_a_bucket_and_no_compile_after_the_warm_up(
        bweights, bhistories):
    from predictionio_tpu.obs import compile_count, install_compile_probe
    install_compile_probe()
    model = SeqRecModel(params=bweights[0], n_items=BCFG.vocab,
                        backbone=bb.config_dict(BCFG))
    enc = PackedEncoder(model, rows=8)
    assert enc.warm() == len(BCFG.token_buckets) == 3
    before = compile_count()
    for hs in (bhistories[:1], bhistories[1:5], bhistories,
               bhistories[-3:]):
        enc(hs)
    assert compile_count() == before


def test_seqrec_train_runs_at_tiny_brumby():
    rng = np.random.default_rng(0)
    n_users, n_items = 64, 40
    us = np.repeat(np.arange(n_users), 9)
    starts = rng.integers(0, n_items, n_users)
    its = (np.repeat(starts, 9) + np.tile(np.arange(9), n_users)) % n_items
    ts = np.tile(np.arange(9), n_users)
    seqs, targets = build_sequences(us, its, ts, n_items=n_items, seq_len=8)
    losses = []
    m = seqrec_train(seqs, targets, n_items=n_items, seq_len=8,
                     batch_size=32, epochs=6, lr=3e-3, seed=0,
                     backbone=str(CONFIGS / "tiny-brumby.json"),
                     losses=losses)
    assert len(losses) == 12 and np.isfinite(losses).all()
    assert np.mean(losses[-3:]) < np.mean(losses[:3])
    assert m.config.vocab == n_items and m.item_emb.shape == (n_items, 64)
    init = bb.init_params(jax.random.PRNGKey(0), m.config)
    for leaf in ("wq", "wk", "w_gate", "b_gate"):
        assert np.abs(m.params["l0"]["retention"][leaf]
                      - np.asarray(init["l0"]["retention"][leaf])
                      ).max() > 0, leaf
