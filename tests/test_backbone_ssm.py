"""The sequence recommender's backbone at the `tiny-nemotron` preset:
every layer ONE block (M E M * E M * E). Mamba-2 mixers of 4 heads of 8
in 2 groups, state 16, 4 taps with a bias, scanned in chunks of 16; 4
query heads on 2 KV heads of 24 with no position; a 16-way router, top
4, 8 two-matrix squared-ReLU experts held (share 0 of 2) and a shared
expert; an untied head. Against the plain reference of the benchmark
(`benchmark/nemotron_reference.py`, which imports nothing of the
program) on seeded weights. A file of its own beside
`tests/test_backbone.py`, whose worker is the test run's longest."""

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT / "benchmark") not in sys.path:
    sys.path.append(str(ROOT / "benchmark"))

import nemotron_datagen                                    # noqa: E402
import nemotron_reference as nref                          # noqa: E402

from predictionio_tpu.ops import backbone as bb            # noqa: E402
from predictionio_tpu.ops import moe, ssm                  # noqa: E402
from predictionio_tpu.ops.attention import attention_reference  # noqa: E402
from predictionio_tpu.ops.seqrec import (                  # noqa: E402
    PackedEncoder, SeqRecModel, build_sequences, seqrec_encode,
    seqrec_train,
)

SEED = 5
CONFIGS = ROOT / "benchmark" / "configs"
NDOC = json.loads((CONFIGS / "tiny-nemotron.json").read_text())
NCFG = bb.config_from_json(NDOC, "tiny-nemotron")
NARCH = nref.arch(NDOC)
# 1 to 3 events: shorter than the convolution's reach; 16, 33, 48: a
# chunk, two and an event, three
NLENGTHS = (40, 1, 2, 7, 3, 17, 16, 33, 48)


def _encoder(params, rows=8, cfg=NCFG):
    model = SeqRecModel(params=params, n_items=cfg.vocab,
                        backbone=bb.config_dict(cfg))
    enc = PackedEncoder(model, rows=rows)
    enc.warm()
    return enc


def _logits(params, vecs):
    return np.asarray(vecs) @ np.asarray(params["head"], np.float32).T


@pytest.fixture(scope="module")
def nweights():
    """(the program's pytree, the reference's dict): the same draws."""
    return (nemotron_datagen.program_params(NDOC, SEED, jnp.float32),
            nemotron_datagen.reference_params(NDOC, SEED))


@pytest.fixture(scope="module")
def nhistories():
    rng = np.random.default_rng(20)
    return [rng.integers(0, NCFG.vocab, n).tolist() for n in NLENGTHS]


@pytest.fixture(scope="module")
def nencoder(nweights):
    return _encoder(nweights[0])


@pytest.fixture(scope="module")
def nweights():
    return (nemotron_datagen.program_params(NDOC, SEED, jnp.float32),
            nemotron_datagen.reference_params(NDOC, SEED))


@pytest.fixture(scope="module")
def nhistories():
    rng = np.random.default_rng(20)
    return [rng.integers(0, NCFG.vocab, n).tolist() for n in NLENGTHS]


@pytest.fixture(scope="module")
def nencoder(nweights):
    return _encoder(nweights[0])


def test_nemotron_tiny_has_every_block_kind_and_the_programs_pytree(
        nweights):
    assert NCFG.layers == (("ssm",), ("ffn_moe",), ("ssm",), ("attn_full",),
                           ("ffn_moe",), ("ssm",), ("attn_full",),
                           ("ffn_moe",))
    assert (NCFG.act, NCFG.shared_width, NCFG.conv_bias, NCFG.conv_kernel,
            NCFG.rotary_dim, NCFG.positions, NCFG.tied) == (
                "relu2", 64, True, 4, 0, 0, False)
    assert (NCFG.n_experts, NCFG.experts_held, NCFG.expert_first,
            NCFG.top_k, NCFG.routed_scale, NCFG.route_eps) == (
                16, 8, 0, 4, 2.5, 1e-20)
    init = bb.init_params(jax.random.PRNGKey(0), NCFG)
    assert jax.tree_util.tree_structure(init) \
        == jax.tree_util.tree_structure(nweights[0])
    # one block a layer: one norm, and no second
    assert set(init["l0"]) == {"norm1", "ssm"}
    assert set(init["l1"]) == {"norm1", "ffn"}
    assert set(init["l3"]) == {"norm1", "attn"}
    assert set(init["l1"]["ffn"]) == {"router", "bias", "w_up", "w_down",
                                      "shared"}
    m = init["l0"]["ssm"]
    np.testing.assert_array_equal(m["d"], 1.0)
    np.testing.assert_array_equal(m["norm"]["g"], 1.0)
    rates, steps = np.exp(m["a_log"]), np.asarray(
        jax.nn.softplus(m["dt_bias"]))
    assert (1.0 <= rates).all() and (rates <= 16.0).all()
    assert (0.9e-3 <= steps).all() and (steps <= 1.1e-1).all()
    assert np.abs(np.asarray(m["conv_bias"])).max() > 0
    assert bb.n_params(NCFG) == sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(init))


def _ref_block(rp, i):
    return {k: jnp.asarray(v) for k, v in rp[f"l{i}"].items()}


@pytest.mark.parametrize("T", [1, 3, 16, 40])
def test_ssm_block_matches_reference(nweights, T):
    pp, rp = nweights
    u = jnp.asarray(np.random.default_rng(21).normal(size=(T, NCFG.hidden)),
                    jnp.float32)
    got = bb.ssm_block(pp["l0"]["ssm"], NCFG, u, jnp.arange(T))
    with jax.default_matmul_precision("highest"):
        want = nref.mamba_mixer(NARCH, _ref_block(rp, 0), u)
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert np.abs(np.asarray(want)).max() > 1e-3


def _scan_case(first_at, T=64, H=4, P=8, G=2, N=16, seed=22):
    rng = np.random.default_rng(seed)
    first = np.zeros(T, bool)
    first[list(first_at)] = True
    # steps up to 1.2 at rates up to 16: a chunk's decays span e^-300
    return (jnp.asarray(rng.normal(size=(T, H, P)), jnp.float32),
            jnp.asarray(np.abs(rng.normal(size=(T, H))) * 0.4, jnp.float32),
            -jnp.asarray(rng.uniform(1, 16, size=H), jnp.float32),
            jnp.asarray(rng.normal(size=(T, G, N)), jnp.float32),
            jnp.asarray(rng.normal(size=(T, G, N)), jnp.float32),
            jnp.asarray(first))


@pytest.mark.parametrize("first_at", [
    (0, 16, 32),            # a history's first event at a chunk's first slot
    (0, 23, 40),            # in its middle
    (0, 31, 47),            # at its last
    (0, 5, 6, 7, 29, 63),   # histories of one event, and one that spans
    (0,),                   # one history over every chunk
], ids=["first", "middle", "last", "mixed", "one"])
def test_chunked_scan_is_the_recurrence_event_by_event(first_at):
    """Histories whose lengths are no multiples of the chunk of 16,
    first events at every kind of slot: the kernel against
    `ssm.scan_steps`, and against each history scanned alone. The
    results reach 13 at unit inputs, so 1e-5 is relative here."""
    x, dt, a, b, c, first = _scan_case(first_at)
    got = ssm.chunk_scan(x, dt, a, b, c, first, 16)
    want = ssm.scan_steps(x, dt, a, b, c, first)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert np.isfinite(np.asarray(got)).all()
    for lo, hi in zip(first_at, first_at[1:] + (64,)):
        alone = ssm.scan_steps(x[lo:hi], dt[lo:hi], a, b[lo:hi], c[lo:hi],
                               jnp.zeros(hi - lo, bool).at[0].set(True))
        np.testing.assert_allclose(got[lo:hi], alone, rtol=1e-5, atol=1e-5)


def test_what_is_packed_in_front_of_a_history_leaves_it_bit_equal():
    """The same events at the same slots behind two different
    strangers: the mask and the cut are exact zeros, so not one bit of
    the history's result moves; without the cut it does."""
    x, dt, a, b, c, first = _scan_case((0, 23))
    other = _scan_case((0, 9, 23), seed=23)
    front = jnp.arange(64) < 23

    def behind(mine, theirs):
        return jnp.where(front.reshape((-1,) + (1,) * (mine.ndim - 1)),
                         theirs, mine)

    got = ssm.chunk_scan(x, dt, a, b, c, first, 16)
    moved = ssm.chunk_scan(*(behind(m, t) for m, t in zip(
        (x, dt), other[:2])), a, *(behind(m, t) for m, t in zip(
            (b, c), other[3:5])), other[5], 16)
    np.testing.assert_array_equal(got[23:], moved[23:])
    assert np.abs(np.asarray(got[:23] - moved[:23])).max() > 1e-2
    uncut = ssm.chunk_scan(x, dt, a, b, c, first.at[23].set(False), 16)
    assert np.abs(np.asarray(uncut[23:] - got[23:])).max() > 1e-3


def test_a_gradient_through_the_chunked_scan_is_the_recurrences():
    x, dt, a, b, c, first = _scan_case((0, 23, 40))
    w = jnp.asarray(np.random.default_rng(24).normal(size=(64, 4, 8)),
                    jnp.float32)
    chunked = jax.grad(lambda *v: (ssm.chunk_scan(*v, first, 16) * w).sum(),
                       argnums=(0, 1, 2, 3, 4))(x, dt, a, b, c)
    steps = jax.grad(lambda *v: (ssm.scan_steps(*v, first) * w).sum(),
                     argnums=(0, 1, 2, 3, 4))(x, dt, a, b, c)
    for g, want in zip(chunked, steps):
        np.testing.assert_allclose(g, want, rtol=1e-5, atol=1e-5)
        assert np.abs(np.asarray(want)).max() > 1e-3


def test_attention_without_position_matches_reference(nweights):
    pp, rp = nweights
    T = 21
    u = jnp.asarray(np.random.default_rng(25).normal(size=(T, NCFG.hidden)),
                    jnp.float32)

    def attend(q, k, v, *, window, sink):
        assert window is None and sink is None
        assert q.shape == (T, 4, 24) and k.shape == v.shape == (T, 2, 24)
        return attention_reference(q[None], k[None], v[None],
                                   causal=True)[0]

    # the positions are handed in and must be read by nothing
    got = [bb.attention_block(pp["l3"]["attn"], NCFG, "attn_full", u, pos,
                              attend)
           for pos in (jnp.arange(T), jnp.arange(T)[::-1])]
    with jax.default_matmul_precision("highest"):
        want = nref.attention(NARCH, _ref_block(rp, 3), u)
    np.testing.assert_allclose(got[0], want, atol=1e-5)
    np.testing.assert_array_equal(got[0], got[1])


@pytest.mark.parametrize("live", [None, "some"])
def test_squared_relu_experts_and_the_shared_one_match_reference(
        nweights, live):
    pp, rp = nweights
    T = 64
    u = jnp.asarray(np.random.default_rng(26).normal(size=(T, NCFG.hidden)),
                    jnp.float32)
    mask = None if live is None else jnp.arange(T) % 5 != 0
    y, stats = bb.ffn_moe(pp["l1"]["ffn"], NCFG, u, live=mask)
    shared = bb.ffn_shared(pp["l1"]["ffn"]["shared"], NCFG, u)
    p = _ref_block(rp, 1)
    with jax.default_matmul_precision("highest"):
        routed = nref.routed_experts(NARCH, p, u, (0, 8))
        want_shared = nref.shared_expert(p, u)
        sel, _ = nref.route(NARCH, p, u)
    if mask is not None:
        routed = jnp.where(mask[:, None], routed, 0.0)
    np.testing.assert_allclose(y, routed, atol=1e-5)
    np.testing.assert_allclose(shared, want_shared, atol=1e-5)
    assert np.abs(np.asarray(routed)).max() > 1e-2
    here = np.asarray(sel) < 8
    if mask is not None:
        here = here & np.asarray(mask)[:, None]
    assert int(stats.expert_tokens.sum()) == here.sum()
    assert int(stats.unrouted) == (
        (~here.any(axis=1)) & (True if mask is None
                               else np.asarray(mask))).sum()
    # half the experts held: one buffer holds every pair of the call
    assert moe.gather_combine(T, NCFG.top_k, 8, 16)


def test_the_two_shares_and_the_shared_expert_once_are_the_uncut_block():
    """Experts 0-7 on one chip, 8-15 on the other, the shared expert
    computed by both and counted once: the reference's block over all
    16 experts."""
    whole = dict(NDOC, n_routed_experts=16,
                 expert_share={"index": 0, "count": 1})
    rp = _ref_block(nemotron_datagen.reference_params(whole, SEED), 1)
    a = nref.arch(whole)
    T = 48
    u = jnp.asarray(np.random.default_rng(27).normal(size=(T, NCFG.hidden)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = (nref.routed_experts(a, rp, u, None)
                + nref.shared_expert(rp, u))
    total, pairs = 0.0, 0
    for index in (0, 1):
        cfg = bb.config_from_json(
            dict(NDOC, expert_share={"index": index, "count": 2}), "half")
        assert (cfg.expert_first, cfg.experts_held) == (8 * index, 8)
        lo = cfg.expert_first
        p = {"router": rp["router"], "bias": rp["bias"],
             "w_up": rp["w_up"][lo:lo + 8], "w_down": rp["w_down"][lo:lo + 8]}
        y, stats = bb.ffn_moe(p, cfg, u)
        total, pairs = total + y, pairs + int(stats.expert_tokens.sum())
    shared = bb.ffn_shared({"w_up": rp["shared_up"],
                            "w_down": rp["shared_down"]}, NCFG, u)
    np.testing.assert_allclose(total + shared, want, atol=1e-5)
    assert pairs == T * NCFG.top_k
    assert np.abs(np.asarray(total + 2 * shared - want)).max() > 1e-2


def test_nemotron_packed_stack_matches_reference_one_history_at_a_time(
        nweights, nencoder, nhistories):
    pp, rp = nweights
    got = _logits(pp, nencoder(nhistories))
    for row, h in zip(got, nhistories):
        np.testing.assert_allclose(row, nref.forward(NDOC, rp, h),
                                   atol=1e-5)


def test_nemotron_a_history_alone_and_packed_behind_others_agree(
        nencoder, nhistories):
    """The state's leak test: histories of 1 to 3 events stand right
    behind one of 40; without the cut they would carry its state."""
    packed = nencoder(nhistories)
    for j, h in enumerate(nhistories):
        np.testing.assert_allclose(nencoder([h])[0], packed[j], atol=1e-5)


def test_nemotron_padded_training_layout_agrees_with_the_packed_one(
        nweights, nencoder, nhistories):
    """Right-aligned rows: the padding in front of a history is
    non-zero after the convolution's bias, and must not reach its
    state."""
    pp, _ = nweights
    S = NCFG.max_history
    seqs = np.full((len(nhistories), S), NCFG.vocab, np.int32)
    for r, h in enumerate(nhistories):
        seqs[r, S - len(h):] = h
    model = SeqRecModel(params=pp, n_items=NCFG.vocab,
                        backbone=bb.config_dict(NCFG))
    np.testing.assert_allclose(seqrec_encode(model, seqs),
                               nencoder(nhistories), atol=1e-5)


def test_nemotron_layerwise_reference_is_the_reference_at_two_lengths(
        nweights, nhistories):
    _, rp = nweights
    assert nref.padded_lengths([3, 40, 128, 129, 500]) == [128, 128, 128,
                                                           512, 512]
    assert nref.padded_lengths([16, 300, 2048]) == [512, 512, 2048]
    layerwise = nref.forward_layerwise(
        NDOC, nemotron_datagen.layer_stream(NDOC, SEED), nhistories)
    for row, h in zip(layerwise, nhistories):
        np.testing.assert_allclose(row, nref.forward(NDOC, rp, h),
                                   atol=2e-5)


@pytest.mark.parametrize("fault", sorted(nref.FAULTS))
def test_nemotron_reference_faults_move_the_logits(nweights, nhistories,
                                                    fault):
    _, rp = nweights
    h = nhistories[0]
    moved = nref.forward(NDOC, rp, h, **{fault: True}) \
        - nref.forward(NDOC, rp, h)
    assert np.abs(moved).max() > 1e-2


def test_nemotron_bfloat16_path_inside_its_tolerance_and_the_control_outside(
        nhistories):
    """As `nemotron_control.py` on the chip, at the toy size and under
    the rehearsal cell's limits: the program in bfloat16 and the
    reference with bfloat16 operands come out correct, float8 operands
    not."""
    import reference
    import seq_control
    limits = json.loads((ROOT / "benchmark" / "workloads"
                         / "rehearse-nemotron-serve.json").read_text())[
                             "correct"]["limits"]
    rp = nemotron_datagen.reference_params(NDOC, SEED)
    truth = np.stack([nref.forward(NDOC, rp, h) for h in nhistories])
    pp = nemotron_datagen.program_params(NDOC, SEED)        # bfloat16
    program = _logits(pp, _encoder(pp)(nhistories))
    with nref.operands("bf16"):
        stated = np.stack([nref.forward(NDOC, rp, h) for h in nhistories])
    with nref.operands("fp8"):
        control = np.stack([nref.forward(NDOC, rp, h) for h in nhistories])
    for got in (program, stated):
        assert reference.verdict(seq_control.numbers(got, truth, 10),
                                 limits)[0]
    n = seq_control.numbers(control, truth, 10)
    assert not reference.verdict(n, limits)[0]
    assert n["score_err_median"] > 3 * limits["score_err_median"]


def test_the_packed_encoder_counts_the_chunks_that_hold_a_first_event(
        nencoder):
    """`pio_seq_ssm_reset_chunk_share`, once a call of a stack with a
    state-space mixer and never of one without, and the per-layer
    metric that reads it."""
    from predictionio_tpu.obs import get_registry
    import readers
    child = get_registry().histogram(
        "pio_seq_ssm_reset_chunk_share").labels()
    n0, s0 = child.count, child.sum
    import lfm2_datagen
    ldoc = json.loads((CONFIGS / "tiny-lfm2.json").read_text())
    _encoder(lfm2_datagen.program_params(ldoc, SEED, jnp.float32),
             cfg=bb.config_from_json(ldoc, "tiny-lfm2"))([[1, 2, 3]])
    assert child.count == n0
    # 20 + 30 + 14 events in a bucket of 64: firsts at 0, 20 and 50,
    # in chunks 0, 1 and 3 of 4
    nencoder([[1] * 20, [2] * 30, [3] * 14])
    assert (child.count - n0, child.sum - s0) == (1, 0.75)
    # 16 + 16 + 8 in a bucket of 64: chunks 0, 1 and 2 (the padding's
    # own first slot is no history's)
    nencoder([[1] * 16, [2] * 16, [3] * 8])
    assert (child.count - n0, child.sum - s0) == (2, 1.5)
    metric = json.loads((ROOT / "benchmark" / "layer_metrics"
                         / "seq_ssm_reset_chunk_pct.json").read_text())
    assert metric["reader"] == "readers.hist_mean"
    facts = {"hist": {"pio_seq_ssm_reset_chunk_share": {
        "count": 2, "sum": 1.5}}}
    assert readers.hist_mean(facts, **metric["args"]) == 75.0
    assert readers.hist_mean({"hist": {}}, **metric["args"]) is None


def test_one_executable_a_bucket_and_no_compile_after_the_warm_up(
        nweights, nhistories):
    from predictionio_tpu.obs import compile_count, install_compile_probe
    install_compile_probe()
    model = SeqRecModel(params=nweights[0], n_items=NCFG.vocab,
                        backbone=bb.config_dict(NCFG))
    enc = PackedEncoder(model, rows=8)
    assert enc.warm() == len(NCFG.token_buckets) == 3
    assert sorted(enc._exe) == [64, 128, 256] and enc.warm() == 0
    before = compile_count()
    for hs in (nhistories[:1], nhistories[1:5], nhistories, nhistories[-3:]):
        enc(hs)
    assert compile_count() == before


def test_seqrec_train_runs_at_tiny_nemotron():
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
                     backbone=str(CONFIGS / "tiny-nemotron.json"),
                     losses=losses)
    assert len(losses) == 12 and np.isfinite(losses).all()
    assert np.mean(losses[-3:]) < np.mean(losses[:3])
    assert m.config.vocab == n_items and m.item_emb.shape == (n_items, 64)
    init = bb.init_params(jax.random.PRNGKey(0), m.config)
    for leaf in ("a_log", "dt_bias", "conv_bias", "kernel", "w_in"):
        assert np.abs(m.params["l0"]["ssm"][leaf]
                      - np.asarray(init["l0"]["ssm"][leaf])).max() > 0, leaf
    assert np.abs(m.params["l1"]["ffn"]["shared"]["w_up"]
                  - np.asarray(init["l1"]["ffn"]["shared"]["w_up"])).max() > 0
    np.testing.assert_array_equal(m.params["l1"]["ffn"]["bias"],
                                  init["l1"]["ffn"]["bias"])

