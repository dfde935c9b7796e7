"""The sequence recommender's backbone at the `tiny-mimo` preset (hidden
64, 4 heads, 1 / 2 KV heads, head sizes 24 / 16 with 8 rotary, window 8,
16 experts top-2 of which 4 held, dense width 128, expert width 32,
vocabulary 97, the 7-layer pattern), against the plain reference of the
benchmark (`benchmark/seq_reference.py`, which imports nothing of the
program) on seeded weights."""

import json
import sys
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT / "benchmark") not in sys.path:
    sys.path.append(str(ROOT / "benchmark"))

import seq_datagen                                         # noqa: E402
import seq_reference as ref                                # noqa: E402

from predictionio_tpu.obs import trace                     # noqa: E402
from predictionio_tpu.ops import backbone as bb            # noqa: E402
from predictionio_tpu.ops import moe                       # noqa: E402
from predictionio_tpu.ops.attention import (               # noqa: E402
    attention_reference, packed_attention,
)
from predictionio_tpu.ops.seqrec import (                  # noqa: E402
    PackedEncoder, SeqRecModel, build_sequences, seqrec_train,
)

SEED = 5
DOC = json.loads((ROOT / "benchmark" / "configs" / "tiny-mimo.json")
                 .read_text())
CFG = bb.config_from_json(DOC, "tiny-mimo")
ARCH = ref.arch(DOC)
LENGTHS = (7, 8, 9, 40)            # around the window of 8


@pytest.fixture(scope="module")
def weights():
    """(the program's pytree, the reference's dict): the same draws."""
    return (seq_datagen.program_params(DOC, SEED, jnp.float32),
            seq_datagen.reference_params(DOC, SEED))


@pytest.fixture(scope="module")
def histories():
    rng = np.random.default_rng(0)
    return [rng.integers(0, CFG.vocab, n).tolist() for n in LENGTHS]


def _encoder(params, rows=8, cfg=None):
    cfg = cfg or CFG
    model = SeqRecModel(params=params, n_items=cfg.vocab,
                        backbone=bb.config_dict(cfg))
    enc = PackedEncoder(model, rows=rows)
    enc.warm()
    return enc


@pytest.fixture(scope="module")
def encoder(weights):
    return _encoder(weights[0])


def _logits(params, vecs):
    return np.asarray(vecs) @ np.asarray(params["head"], np.float32).T


# -- each block kind against the reference -----------------------------------

@pytest.mark.parametrize("layer,kind", [(0, "attn_full"), (2, "attn_window")])
def test_attention_block_matches_reference(weights, layer, kind):
    pp, rp = weights
    assert CFG.layers[layer][0] == kind
    T = 21
    u = jnp.asarray(np.random.default_rng(1).normal(size=(T, CFG.hidden)),
                    jnp.float32)
    seg, start = jnp.zeros(T, jnp.int32), jnp.zeros(T, jnp.int32)

    def attend(q, k, v, *, window, sink):
        pad = 32 - T          # the packed kernel takes whole blocks
        q, k, v = (jnp.pad(x, ((0, pad), (0, 0), (0, 0))) for x in (q, k, v))
        s = jnp.concatenate([seg, jnp.ones(pad, jnp.int32)])
        st = jnp.concatenate([start, jnp.full(pad, T, jnp.int32)])
        return packed_attention(q, k, v, s, st, window=window, sink=sink,
                                max_segment=CFG.max_history, block_q=8,
                                block_k=8)[:T]

    got = bb.attention_block(pp[f"l{layer}"]["attn"], CFG, kind, u,
                             jnp.arange(T), attend)
    p = {k: jnp.asarray(v) for k, v in rp[f"l{layer}"].items()}
    with jax.default_matmul_precision("highest"):
        want = ref.attention(ARCH, ARCH["layers"][layer][0], p, u)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_dense_block_matches_reference(weights):
    pp, rp = weights
    u = jnp.asarray(np.random.default_rng(2).normal(size=(13, CFG.hidden)),
                    jnp.float32)
    p = {k: jnp.asarray(v) for k, v in rp["l0"].items()}
    np.testing.assert_allclose(bb.ffn_dense(pp["l0"]["ffn"], CFG, u),
                               ref.dense_ffn(p, u), atol=1e-5)


def test_expert_block_matches_reference_share(weights):
    pp, rp = weights
    u = jnp.asarray(np.random.default_rng(3).normal(size=(50, CFG.hidden)),
                    jnp.float32)
    got, stats = bb.ffn_moe(pp["l1"]["ffn"], CFG, u)
    p = {k: jnp.asarray(v) for k, v in rp["l1"].items()}
    want = ref.expert_ffn(ARCH, p, u, (ARCH["first"], ARCH["held"]))
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert np.abs(np.asarray(want)).max() > 1e-3
    sel, _ = ref.route(ARCH, p, u)
    held = (np.asarray(sel) >= ARCH["first"]) \
        & (np.asarray(sel) < ARCH["first"] + ARCH["held"])
    assert int(stats.unrouted) == int((~held.any(axis=1)).sum())
    np.testing.assert_array_equal(
        stats.expert_tokens,
        [(np.asarray(sel) == ARCH["first"] + e).sum()
         for e in range(ARCH["held"])])


# -- the whole stack, packed --------------------------------------------------

def test_packed_stack_matches_reference_one_history_at_a_time(
        weights, encoder, histories):
    pp, rp = weights
    got = _logits(pp, encoder(histories))
    for row, h in zip(got, histories):
        np.testing.assert_allclose(row, ref.forward(DOC, rp, h), atol=1e-5)


def test_a_history_alone_and_packed_among_others_agree(encoder, histories):
    enc = encoder
    packed = enc(histories)
    for j, h in enumerate(histories):
        np.testing.assert_allclose(enc([h])[0], packed[j], atol=1e-5)


def test_calls_split_at_the_token_budget_and_stay_in_order(encoder):
    enc = encoder
    rng = np.random.default_rng(4)
    hs = [rng.integers(0, CFG.vocab, n).tolist()
          for n in (48, 48, 48, 48, 48, 48, 3, 5, 7, 9, 11, 2, 2, 2, 2, 2)]
    calls = enc._calls(hs)
    assert [h for c in calls for h in c] == hs
    assert all(sum(map(len, c)) <= enc.max_tokens and len(c) <= enc.rows
               for c in calls) and len(calls) > 2
    assert any(len(c) == enc.rows for c in calls)
    got = enc(hs)
    np.testing.assert_allclose(got[6], enc([hs[6]])[0], atol=1e-5)


def test_layerwise_reference_is_the_reference(weights, histories):
    _, rp = weights
    layerwise = ref.forward_layerwise(
        DOC, seq_datagen.layer_stream(DOC, SEED), histories)
    for row, h in zip(layerwise, histories):
        np.testing.assert_allclose(row, ref.forward(DOC, rp, h), atol=2e-5)


# -- the attention kernel's arguments ----------------------------------------

def _packed_case(window, sink, T=64, H=4, Hkv=2, Dq=24, Dv=16):
    rng = np.random.default_rng(7)
    lens = [7, 8, 9, 25]
    pad = T - sum(lens)
    seg = np.concatenate([np.full(n, i) for i, n in enumerate(lens + [pad])])
    start = np.concatenate([np.full(n, s) for n, s in zip(
        lens + [pad], np.cumsum([0] + lens))])
    q = jnp.asarray(rng.normal(size=(T, H, Dq)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(T, Hkv, Dq)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(T, Hkv, Dv)), jnp.float32)
    seg, start = jnp.asarray(seg, jnp.int32), jnp.asarray(start, jnp.int32)
    got = packed_attention(q, k, v, seg, start, window=window, sink=sink,
                           max_segment=25, block_q=8, block_k=8)
    want = attention_reference(q[None], k[None], v[None], causal=True,
                               window=window, sink=sink,
                               segment_ids=seg[None])[0]
    return got, want, sum(lens)


_SINK = jnp.asarray([0.3, -1.2, 2.0, 0.0], jnp.float32)


@pytest.mark.parametrize("window,sink", [(None, None), (8, None),
                                         (None, _SINK), (8, _SINK)])
def test_packed_attention_matches_plain(window, sink):
    got, want, live = _packed_case(window, sink)
    np.testing.assert_allclose(got[:live], want[:live], atol=1e-5)


def test_sink_at_minus_infinity_is_no_sink_and_a_finite_one_is_not():
    none, _, live = _packed_case(8, None)
    minus_inf, _, _ = _packed_case(8, jnp.full((4,), -jnp.inf))
    finite, _, _ = _packed_case(8, _SINK)
    np.testing.assert_array_equal(none[:live], minus_inf[:live])
    assert np.abs(np.asarray(finite - none)[:live]).max() > 1e-2


def test_the_window_counts_the_query_itself():
    got8, _, live = _packed_case(8, None)
    got9, _, _ = _packed_case(9, None)
    # token 6 of the first history (7 events) sees all 7 under both;
    # token 8 of the third (9 events) sees 8 under window 8, 9 under 9
    np.testing.assert_allclose(got8[6], got9[6], atol=1e-6)
    assert np.abs(np.asarray(got8[7 + 8 + 8] - got9[7 + 8 + 8])).max() > 1e-4


# -- the router and the shares ------------------------------------------------

def test_selection_uses_s_plus_c_and_weights_use_s():
    u = jnp.eye(4, dtype=jnp.float32)[:1] * 0.0          # s = 0.5 everywhere
    w = jnp.zeros((4, 6), jnp.float32)
    c = jnp.asarray([0.0, 0.3, 0.0, 0.2, 0.0, 0.1], jnp.float32)
    r = moe.route(u, w, c, top_k=2, norm_topk_prob=False)
    assert sorted(np.asarray(r.experts[0]).tolist()) == [1, 3]
    np.testing.assert_allclose(r.weights[0], [0.5, 0.5])  # s, not s + c
    r = moe.route(u, w, c, top_k=2)
    np.testing.assert_allclose(r.weights[0], [0.5, 0.5])  # normalised s


@pytest.mark.parametrize("live", [None, "some"])
def test_tokens_over_experts_sum_to_t_times_k(weights, live):
    pp, _ = weights
    f = pp["l1"]["ffn"]
    T = 37
    u = jnp.asarray(np.random.default_rng(5).normal(size=(T, CFG.hidden)),
                    jnp.float32)
    mask = None if live is None else jnp.arange(T) < 30
    routing = moe.route(u, f["router"], f["bias"], top_k=CFG.top_k)
    total = 0
    for share in range(4):
        first, held = moe.expert_share(share, 4, CFG.n_experts)
        _, stats = moe.moe_apply(u, routing, f["w_gate_up"], f["w_down"],
                                 first=first, n_experts=CFG.n_experts,
                                 live=mask)
        total += int(stats.expert_tokens.sum())
    assert total == (T if live is None else 30) * CFG.top_k


def test_the_four_shares_add_up_to_the_uncut_expert_layer():
    """All 16 experts drawn, each share's part computed by the program
    with its own 4, and the parts summed: the reference's whole layer."""
    whole = dict(DOC, n_routed_experts=16,
                 expert_share={"index": 0, "count": 1})
    a = ref.arch(whole)
    p = {k: jnp.asarray(v) for k, v in
         seq_datagen.reference_params(whole, SEED)["l1"].items()}
    u = jnp.asarray(np.random.default_rng(6).normal(size=(45, CFG.hidden)),
                    jnp.float32)
    want = ref.expert_ffn(a, p, u, None)
    routing = moe.route(u, p["router"], p["bias"], top_k=CFG.top_k)
    total = np.zeros_like(np.asarray(want))
    for share in range(4):
        first, held = moe.expert_share(share, 4, 16)
        part, _ = moe.moe_apply(
            u, routing, p["w_gate_up"][first:first + held],
            p["w_down"][first:first + held], first=first, n_experts=16)
        assert np.abs(np.asarray(part)).max() > 1e-4
        total += np.asarray(part)
    np.testing.assert_allclose(total, want, atol=1e-5)


def test_more_pairs_than_one_buffer_holds_are_all_computed(weights):
    """Every token's two experts held here: twice the buffer."""
    pp, _ = weights
    f = pp["l1"]["ffn"]
    T = 24
    u = jnp.asarray(np.random.default_rng(8).normal(size=(T, CFG.hidden)),
                    jnp.float32)
    routing = moe.Routing(jnp.tile(jnp.asarray([[0, 3]], jnp.int32), (T, 1)),
                          jnp.full((T, 2), 0.5, jnp.float32))
    got, stats = moe.moe_apply(u, routing, f["w_gate_up"], f["w_down"],
                               first=0, n_experts=CFG.n_experts)
    want = 0
    for e in (0, 3):
        gu = u @ f["w_gate_up"][e]
        F = CFG.expert_width
        want = want + 0.5 * (jax.nn.silu(gu[:, :F]) * gu[:, F:]) \
            @ f["w_down"][e]
    assert int(stats.expert_tokens.sum()) == 2 * T
    np.testing.assert_allclose(got, want, atol=1e-5)


# -- precision: the stated one inside, the one below outside ------------------

def test_bfloat16_path_inside_its_tolerance_and_the_control_outside(
        histories):
    """As `seq_control.py` on the chip, at the toy size: the program in
    bfloat16 and the reference with bfloat16 operands read alike and
    under the cell's limits; float8 operands read over one."""
    import seq_control
    limits = json.loads((ROOT / "benchmark" / "workloads"
                         / "mimo25-hist-c32.json").read_text())[
                             "correct"]["limits"]
    rp = seq_datagen.reference_params(DOC, SEED)
    truth = np.stack([ref.forward(DOC, rp, h) for h in histories])
    pp = seq_datagen.program_params(DOC, SEED)             # bfloat16
    program = _logits(pp, _encoder(pp)(histories))
    with ref.operands("bf16"):
        stated = np.stack([ref.forward(DOC, rp, h) for h in histories])
    with ref.operands("fp8"):
        control = np.stack([ref.forward(DOC, rp, h) for h in histories])
    k = 10
    for got in (program, stated):
        n = seq_control.numbers(got, truth, k)
        assert n["score_err"] <= limits["score_err"]
        assert n["rank_gap"] <= limits["rank_gap"]
    n = seq_control.numbers(control, truth, k)
    assert max(n["score_err"] / limits["score_err"],
               n["rank_gap"] / limits["rank_gap"]) > 1.0


# -- configurations -----------------------------------------------------------

CONFIGS = ROOT / "benchmark" / "configs"


@pytest.mark.parametrize("name", ["tiny-mimo", "mimo-v2.5-ep16-7l"])
def test_a_configuration_is_its_file_and_survives_the_model_blob(name):
    doc = json.loads((CONFIGS / f"{name}.json").read_text())
    cfg = bb.load_config(str(CONFIGS / f"{name}.json"))
    assert cfg == bb.config_from_json(doc, name) and cfg.name == name
    assert bb.config_of(json.loads(json.dumps(bb.config_dict(cfg)))) == cfg
    with pytest.raises(ValueError):
        bb.load_config(name)               # a name is no file


def test_published_widths_and_the_cut():
    cfg = bb.load_config(str(CONFIGS / "mimo-v2.5-ep16-7l.json"))
    assert (cfg.hidden, cfg.n_heads, cfg.kv_heads_full, cfg.kv_heads_window,
            cfg.qk_dim, cfg.v_dim, cfg.rotary_dim, cfg.window) == (
                4096, 64, 4, 8, 192, 128, 64, 128)
    assert (cfg.n_experts, cfg.top_k, cfg.experts_held, cfg.expert_width,
            cfg.dense_width, cfg.vocab) == (256, 8, 16, 2048, 16384, 19072)
    assert cfg.layers == (("attn_full", "ffn_dense"),
                          ("attn_full", "ffn_moe")) \
        + (("attn_window", "ffn_moe"),) * 5
    assert bb.n_params(cfg) == 3_429_955_392


def test_sasrec_is_a_configuration_of_the_same_stack():
    cfg = bb.sasrec_config(dim=16, n_heads=2, n_layers=2, seq_len=8,
                           n_items=30)
    p = bb.init_params(jax.random.PRNGKey(0), cfg)
    assert set(p) == {"embed", "pos", "norm_f", "l0", "l1"}
    assert p["embed"].shape == (31, 16) and "head" not in p
    assert set(p["l0"]["ffn"]) == {"w1", "w2"}
    assert bb.config_of(bb.config_dict(cfg)) == cfg


# -- training at the toy preset -----------------------------------------------

def test_seqrec_train_smoke_at_the_tiny_preset():
    rng = np.random.default_rng(0)
    n_users, n_items = 96, 40
    us = np.repeat(np.arange(n_users), 9)
    starts = rng.integers(0, n_items, n_users)
    its = (np.repeat(starts, 9) + np.tile(np.arange(9), n_users)) % n_items
    ts = np.tile(np.arange(9), n_users)
    seqs, targets = build_sequences(us, its, ts, n_items=n_items, seq_len=8)
    losses = []
    m = seqrec_train(seqs, targets, n_items=n_items, seq_len=8,
                     batch_size=48, epochs=10, lr=3e-3, seed=0,
                     backbone=str(CONFIGS / "tiny-mimo.json"), losses=losses)
    assert len(losses) == 20 and np.isfinite(losses).all()
    assert np.mean(losses[-4:]) < 0.8 * np.mean(losses[:4])
    assert m.config.vocab == n_items and m.item_emb.shape == (n_items, 64)
    # the correction bias is held constant (a departure: its balancing
    # update is no gradient step)
    init = bb.init_params(jax.random.PRNGKey(0), m.config)
    np.testing.assert_array_equal(m.params["l1"]["ffn"]["bias"],
                                  init["l1"]["ffn"]["bias"])
    assert np.abs(m.params["l1"]["ffn"]["router"]
                  - np.asarray(init["l1"]["ffn"]["router"])).max() > 0


# -- the serve path -----------------------------------------------------------

@pytest.fixture
def served(weights):
    """A PredictionServer over a tiny-mimo instance, histories in the
    MEM store, loaded through prepare_deploy -> warm_deploy."""
    from drivers.http_closed import _store_instance
    from drivers.seq_http_closed import write_histories
    from predictionio_tpu.core import EngineParams, workflow
    from predictionio_tpu.data.storage import StorageRegistry
    from predictionio_tpu.ingest.bimap import BiMap
    from predictionio_tpu.models import seqrec as sr
    from predictionio_tpu.obs.metrics import MetricsRegistry
    from predictionio_tpu.serving import PredictionServer, ServerConfig

    pp, rp = weights
    registry = StorageRegistry({
        "PIO_STORAGE_SOURCES_MEM_TYPE": "MEM",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM"})
    lengths = np.asarray([3, 8, 9, 40, 48, 17, 1, 30])
    items = seq_datagen.histories(lengths, CFG.vocab, 1.1, SEED)
    write_histories(registry, lengths, items)
    model = sr.SeqRecServingModel(
        SeqRecModel(params=pp, n_items=CFG.vocab,
                    backbone=bb.config_dict(CFG)),
        BiMap({f"u{n}": n for n in range(len(lengths))}),
        BiMap({f"i{n}": n for n in range(CFG.vocab)}))
    params = EngineParams(
        data_source_params=("", sr.DataSourceParams(app_name="benchapp")),
        algorithm_params_list=(("seqrec", sr.SeqRecParams(
            app_name="benchapp", event_names=("view",),
            backbone=str(CONFIGS / "tiny-mimo.json"))),))
    _store_instance(registry, params)
    original = workflow.deserialize_models
    workflow.deserialize_models = lambda *a, **k: [model]
    try:
        srv = PredictionServer(
            ServerConfig(ip="127.0.0.1", port=0, batch_window_ms=5,
                         batch_max=8),
            registry=registry, engine=sr.engine(),
            metrics=MetricsRegistry())
    finally:
        workflow.deserialize_models = original
    srv.start()
    ends = np.cumsum(lengths)
    hists = [items[e - n:e] for e, n in zip(ends, lengths)]
    try:
        yield srv, hists, rp
    finally:
        srv.shutdown()


def call(port, method, path, body=None):
    import urllib.request
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", method=method,
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req) as resp:
        return resp.status, json.loads(resp.read().decode())


def _hammer(port, users, each=4):
    out = {}

    def one(n):
        for j in range(each):
            u = (n + j) % users
            body = {"user": f"u{u}", "num": 5}
            if (n + j) % 3 == 0:
                body["blackList"] = ["i1", "i2"]
            status, reply = call(port, "POST", "/queries.json", body)
            assert status == 200
            out[(u, "blackList" in body)] = reply
    threads = [threading.Thread(target=one, args=(n,)) for n in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return out


def _drained(srv, timeout=10.0):
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        if not srv._batcher._draining:
            return
        time.sleep(0.005)
    raise AssertionError("the drainer did not retire")


def test_queries_through_the_server_compile_nothing_and_tile_the_cycle(
        served):
    from predictionio_tpu.obs import get_registry
    from predictionio_tpu.obs.jaxprobe import compile_watch
    srv, hists, rp = served
    before = {name: get_registry().histogram(name).labels().count
              for name in ("pio_seq_call_tokens", "pio_seq_pad_share",
                           "pio_seq_history_events",
                           "pio_moe_expert_tokens_max_over_mean",
                           "pio_moe_unrouted_share",
                           "pio_moe_expert_pairs",
                           "pio_moe_gather_combine_share")}
    with compile_watch() as w:
        replies = _hammer(srv.port, len(hists))
    _drained(srv)
    assert w.count == 0
    # what was served is the reference's top of the user's history
    for (u, banned), reply in replies.items():
        logits = ref.forward(DOC, rp, hists[u]).copy()
        if banned:
            logits[[1, 2]] = -np.inf
        want = np.argsort(-logits, kind="stable")[:5]
        got = [int(s["item"][1:]) for s in reply["itemScores"]]
        assert got == want.tolist()
        np.testing.assert_allclose([s["score"] for s in reply["itemScores"]],
                                   logits[want], atol=1e-4)
    # the stages tile the cycle, the sequence model's own among them
    tot = {key[0]: (child.count, child.sum)
           for key, child in srv._serve_obs.stage._items()}
    cycles, cycle_s = tot["cycle"]
    leaves = [n for n in trace.STAGES if n != "predict"]
    assert abs(sum(tot[n][1] for n in leaves) - cycle_s) <= 0.02 * cycle_s
    children = trace.SEQ_STAGES + ("lookup", "pack", "launch", "fetch",
                                   "unpack")
    assert abs(sum(tot[n][1] for n in children) - tot["predict"][1]) \
        <= 0.02 * tot["predict"][1]
    assert tot["host"][1] == pytest.approx(
        cycle_s - tot["fetch"][1] - tot["seq_fetch"][1], rel=1e-9)
    for name in trace.STAGES + ("cycle", "host"):
        assert tot[name][0] == cycles >= 1, name
    # every new counter observed
    for name, n0 in before.items():
        assert get_registry().histogram(name).labels().count > n0, name


def test_status_and_the_plan_gauge_show_the_sequence_templates_plan(served):
    """The sequence template keeps (model, encoder, plan) where the
    others keep `_serve_plan`; what looks at a deployment from outside
    asks `Algorithm.serve_plans()` and so sees it too."""
    import urllib.request
    srv, _, _ = served
    algo, model = srv._dep.algos[0], srv._dep.models[0]
    (plan,) = algo.serve_plans()
    assert plan is algo._plans(model)[1]
    with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/status.json") as resp:
        (shown,) = json.loads(resp.read())["servePlans"]
    assert shown["algorithm"] == "SeqRecAlgorithm"
    assert shown["plan"] == "BucketedTopK" and shown["shards"] == 1
    assert shown["buckets"] == {str(b): "xla" for b in (1, 2, 4, 8)}
    srv._sample_plan_bytes()
    device = next(iter(plan.factors.devices()))
    assert srv.metrics.value(
        "pio_plan_resident_bytes", device=f"{device.platform}:{device.id}",
        bucket="factors") == CFG.vocab * CFG.hidden * 4


def test_a_user_without_history_gets_an_empty_reply(served):
    srv, _, _ = served
    status, reply = call(srv.port, "POST", "/queries.json",
                         {"user": "nobody", "num": 5})
    assert status == 200 and reply["itemScores"] == []


@pytest.mark.parametrize("body,allowed", [
    # a ban list wider than the plan's 64: the index-list entry point
    ({"blackList": [f"i{n}" for n in range(70)]}, range(70, 97)),
    # a whitelist: the dense-mask entry point
    ({"whiteList": ["i3", "i5", "i8", "i13"]}, (3, 5, 8, 13)),
])
def test_filters_the_plan_does_not_take_are_served_the_generic_way(
        served, body, allowed):
    srv, hists, rp = served
    status, reply = call(srv.port, "POST", "/queries.json",
                         {"user": "u3", "num": 4, **body})
    assert status == 200
    logits = ref.forward(DOC, rp, hists[3])
    allowed = np.asarray(allowed)
    want = allowed[np.argsort(-logits[allowed], kind="stable")[:4]]
    assert [int(s["item"][1:]) for s in reply["itemScores"]] \
        == want.tolist()


# == the LFM2 family at `tiny-lfm2` ==========================================
# hidden 64, 4 heads on 2 KV heads of 16 with q/k norm and whole-head
# rotary, gated short convolutions of 3 taps, 8 experts top-2 ALL held,
# a tied table; against `benchmark/lfm2_reference.py`.

import lfm2_datagen                                        # noqa: E402
import lfm2_reference as lref                              # noqa: E402

from predictionio_tpu.ops.seqrec import seqrec_encode      # noqa: E402

LDOC = json.loads((ROOT / "benchmark" / "configs" / "tiny-lfm2.json")
                  .read_text())
LCFG = bb.config_from_json(LDOC, "tiny-lfm2")
LARCH = lref.arch(LDOC)
# 1 and 2 events: shorter than the convolution's reach
LLENGTHS = (40, 1, 2, 7, 3, 17)


@pytest.fixture(scope="module")
def lweights():
    return (lfm2_datagen.program_params(LDOC, SEED, jnp.float32),
            lfm2_datagen.reference_params(LDOC, SEED))


@pytest.fixture(scope="module")
def lhistories():
    rng = np.random.default_rng(10)
    return [rng.integers(0, LCFG.vocab, n).tolist() for n in LLENGTHS]


def _lencoder(params):
    return _encoder(params, cfg=LCFG)


@pytest.fixture(scope="module")
def lencoder(lweights):
    return _lencoder(lweights[0])


def _llogits(params, vecs):
    return np.asarray(vecs) @ np.asarray(params["embed"], np.float32).T


def _ref_layer(rp, i):
    return {k: jnp.asarray(v) for k, v in rp[f"l{i}"].items()}


def test_lfm2_tiny_has_every_block_kind_and_the_programs_pytree(lweights):
    assert {m for m, _ in LCFG.layers} == {"conv", "attn_full"}
    assert {f for _, f in LCFG.layers} == {"ffn_dense", "ffn_moe"}
    assert LCFG.experts_held == LCFG.n_experts == 8 and LCFG.tied
    assert (LCFG.qk_norm, LCFG.conv_kernel, LCFG.route_eps,
            LCFG.rotary_dim) == (True, 3, 1e-6, LCFG.qk_dim)
    init = bb.init_params(jax.random.PRNGKey(0), LCFG)
    assert jax.tree_util.tree_structure(init) \
        == jax.tree_util.tree_structure(lweights[0])
    assert "head" not in init
    np.testing.assert_array_equal(init["l2"]["attn"]["q_norm"]["g"], 1.0)
    assert float(jnp.std(init["l0"]["conv"]["kernel"])) \
        == pytest.approx(3 ** -0.5, rel=0.15)


@pytest.mark.parametrize("T", [1, 2, 21])
def test_conv_mixer_matches_reference(lweights, T):
    pp, rp = lweights
    u = jnp.asarray(np.random.default_rng(11).normal(size=(T, LCFG.hidden)),
                    jnp.float32)
    got = bb.conv_block(pp["l0"]["conv"], LCFG, u, jnp.arange(T))
    with jax.default_matmul_precision("highest"):
        want = lref.conv_mixer(LARCH, _ref_layer(rp, 0), u)
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert np.abs(np.asarray(want)).max() > 1e-3


def test_conv_mixer_keeps_a_tap_only_inside_its_own_history(lweights):
    """Three histories end to end: each as if alone; and the same with
    the taps' mask taken away reads the history in front."""
    pp, rp = lweights
    lens = (5, 1, 9)
    u = jnp.asarray(np.random.default_rng(12).normal(
        size=(sum(lens), LCFG.hidden)), jnp.float32)
    pos = jnp.concatenate([jnp.arange(n) for n in lens])
    got = bb.conv_block(pp["l0"]["conv"], LCFG, u, pos)
    with jax.default_matmul_precision("highest"):
        want = jnp.concatenate([
            lref.conv_mixer(LARCH, _ref_layer(rp, 0), u[a:a + n])
            for a, n in zip(np.cumsum((0,) + lens[:-1]), lens)])
    np.testing.assert_allclose(got, want, atol=1e-5)
    unmasked = bb.conv_block(pp["l0"]["conv"], LCFG, u,
                             jnp.full_like(pos, 10))
    assert np.abs(np.asarray(unmasked - want))[5:7].max() > 1e-2


def test_qk_normed_attention_block_matches_reference(lweights):
    pp, rp = lweights
    T = 21
    u = jnp.asarray(np.random.default_rng(13).normal(size=(T, LCFG.hidden)),
                    jnp.float32)

    def attend(q, k, v, *, window, sink):
        assert window is None and sink is None
        pad = 32 - T
        q, k, v = (jnp.pad(x, ((0, pad), (0, 0), (0, 0))) for x in (q, k, v))
        s = jnp.concatenate([jnp.zeros(T, jnp.int32),
                             jnp.ones(pad, jnp.int32)])
        st = jnp.concatenate([jnp.zeros(T, jnp.int32),
                              jnp.full(pad, T, jnp.int32)])
        return packed_attention(q, k, v, s, st, max_segment=LCFG.max_history,
                                block_q=8, block_k=8)[:T]

    got = bb.attention_block(pp["l2"]["attn"], LCFG, "attn_full", u,
                             jnp.arange(T), attend)
    with jax.default_matmul_precision("highest"):
        want = lref.attention(LARCH, _ref_layer(rp, 2), u)
        bare = lref.attention(LARCH, _ref_layer(rp, 2), u,
                              drop_qk_norm=True)
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert np.abs(np.asarray(bare - want)).max() > 1e-3


def _unwritten_rows_are_nan(monkeypatch):
    """On the chip `grouped_matmul` never writes the rows past the used
    blocks, and they hold whatever lay there: here they hold NaN."""
    real = moe.grouped_matmul

    def poisoned(x, w, block_expert, n_used, block_rows, *rest):
        y = real(x, w, block_expert, n_used, block_rows, *rest)
        block = jnp.arange(x.shape[0]) // block_rows
        return jnp.where((block < n_used)[:, None], y, jnp.nan)

    monkeypatch.setattr(moe, "grouped_matmul", poisoned)


@pytest.mark.parametrize("one_buffer", [True, False])
@pytest.mark.parametrize("live", [None, "some"])
@pytest.mark.parametrize("routed", ["by_the_router", "to_two_experts"])
def test_every_expert_held_counts_t_times_k_and_matches_reference(
        lweights, monkeypatch, one_buffer, live, routed):
    """All 8 experts of 8: one buffer of all T k pairs, combined by the
    gather. The same 8 held of a router said to have 32: a buffer of T
    pairs, so the T k pairs pass it, a second one runs and the rows
    are scatter-added. With padding tokens, whose pairs have no row,
    and with every token sent to two experts, which leaves whole
    blocks of the buffer unused and unwritten."""
    _unwritten_rows_are_nan(monkeypatch)
    pp, rp = lweights
    f = pp["l2"]["ffn"]
    T, n_live = 50, 50 if live is None else 41
    u = jnp.asarray(np.random.default_rng(14).normal(size=(T, LCFG.hidden)),
                    jnp.float32)
    mask = None if live is None else jnp.arange(T) < n_live
    routing = moe.route(u, f["router"], f["bias"], top_k=LCFG.top_k,
                        eps=LCFG.route_eps)
    if routed == "to_two_experts":
        routing = moe.Routing(
            jnp.tile(jnp.asarray([[5, 2]], jnp.int32), (T, 1)),
            routing.weights)
    n_experts = LCFG.n_experts if one_buffer else 32
    got, stats = moe.moe_apply(u, routing, f["w_gate_up"], f["w_down"],
                               first=0, n_experts=n_experts, live=mask)
    assert moe.buffer_pairs(T, LCFG.top_k, 8, 8) == T * LCFG.top_k
    assert moe.buffer_pairs(T, LCFG.top_k, 8, 32) == T
    assert moe.gather_combine(T, LCFG.top_k, 8, n_experts) == one_buffer
    assert int(stats.expert_tokens.sum()) == n_live * LCFG.top_k
    assert int(stats.unrouted) == 0
    if routed == "to_two_experts":
        assert sorted(np.flatnonzero(stats.expert_tokens)) == [2, 5]
        # 10 of the 17 blocks of 12 rows used
        assert moe.moe_block_rows(T) == 12
    F = LCFG.expert_width
    want = np.zeros((T, LCFG.hidden), np.float32)
    for j in range(LCFG.top_k):
        for e in np.unique(routing.experts[:, j]):
            gu = u @ f["w_gate_up"][e]
            y = (jax.nn.silu(gu[:, :F]) * gu[:, F:]) @ f["w_down"][e]
            want += np.where(routing.experts[:, j, None] == e,
                             routing.weights[:, j, None] * y, 0.0)
    want[n_live:] = 0.0
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got)[n_live:], 0.0)
    if routed == "by_the_router":
        ref_out = lref.expert_ffn(LARCH, _ref_layer(rp, 2), u)
        np.testing.assert_allclose(got[:n_live], ref_out[:n_live],
                                   atol=1e-5)
        sel, w = lref.route(LARCH, _ref_layer(rp, 2), u)
        np.testing.assert_array_equal(np.sort(routing.experts, axis=1),
                                      np.sort(sel, axis=1))
        assert float(np.asarray(w).sum(axis=1).max()) < 1.0     # the 1e-6


@pytest.mark.parametrize("held,n_experts,top_k,gather", [
    (32, 32, 4, True),       # lfm2-8b-a1b-pp2-12l: every expert
    (16, 32, 4, True),       # a half: buffer_pairs(T, 4, 16, 32) == 4 T
    (8, 32, 4, False),       # a quarter: 2 T pairs a buffer
    (16, 256, 8, False),     # mimo-v2.5-ep16-7l: a sixteenth
    (8, 8, 2, True),         # tiny-lfm2
    (4, 16, 2, False),       # tiny-mimo
])
def test_the_gather_combine_is_chosen_by_the_buffer_rule(
        held, n_experts, top_k, gather):
    for T in (64, 8192):
        assert moe.gather_combine(T, top_k, held, n_experts) == gather
        assert gather == (moe.buffer_pairs(T, top_k, held, n_experts)
                          == T * top_k)


def test_buffer_pairs_is_mimos_rule_and_never_more_than_every_pair():
    # a sixteenth of 256 experts held, 8 selected: T pairs, 8 buffers
    assert moe.buffer_pairs(8192, 8, 16, 256) == 8192
    assert moe.buffer_pairs(64, 2, 4, 16) == 64               # tiny-mimo
    assert moe.buffer_pairs(8192, 4, 32, 32) == 4 * 8192       # all held
    assert moe.buffer_pairs(8192, 4, 16, 32) == 4 * 8192       # a half
    assert moe.buffer_pairs(8192, 4, 4, 32) == 8192


@pytest.mark.parametrize("which,share,pct", [("tiny-lfm2", 1.0, 100.0),
                                             ("tiny-mimo", 0.0, 0.0)])
def test_the_packed_encoder_says_which_combine_a_call_ran(
        encoder, lencoder, histories, lhistories, which, share, pct):
    """`pio_moe_gather_combine_share`, once a call, and the per-layer
    metric that reads it for both sequence cells."""
    from predictionio_tpu.obs import get_registry
    import readers
    enc, hs = {"tiny-lfm2": (lencoder, lhistories),
               "tiny-mimo": (encoder, histories)}[which]
    child = get_registry().histogram("pio_moe_gather_combine_share").labels()
    calls = get_registry().histogram("pio_seq_call_tokens").labels()
    n0, s0, c0 = child.count, child.sum, calls.count
    enc(hs)
    assert child.count - n0 == calls.count - c0 >= 1
    assert child.sum - s0 == share * (child.count - n0)
    bench_dir = ROOT / "benchmark"
    metric = json.loads((bench_dir / "layer_metrics"
                         / "seq_moe_gather_combine_pct.json").read_text())
    assert metric["reader"] == "readers.hist_mean"
    facts = {"hist": {"pio_moe_gather_combine_share": {
        "count": child.count - n0, "sum": child.sum - s0}}}
    assert readers.hist_mean(facts, **metric["args"]) == pct
    assert readers.hist_mean({"hist": {}}, **metric["args"]) is None
    entry = [m for m in json.loads((ROOT / "BENCHMARK.json").read_text())
             ["per_layer"] if m["name"] == "seq_moe_gather_combine_pct"]
    assert entry == [{
        "name": "seq_moe_gather_combine_pct", "unit": "%",
        "better": "higher", "source": "program_counter",
        "layer": "expert layer", "moves": "serve_qps",
        "workloads": ["lfm2-hist-c32", "mimo25-hist-c32",
                      "nemotron-tt-hist-c32"]}]


def test_lfm2_packed_stack_matches_reference_one_history_at_a_time(
        lweights, lencoder, lhistories):
    pp, rp = lweights
    got = _llogits(pp, lencoder(lhistories))
    for row, h in zip(got, lhistories):
        np.testing.assert_allclose(row, lref.forward(LDOC, rp, h),
                                   atol=1e-5)


def test_lfm2_a_history_alone_and_packed_behind_others_agree(
        lencoder, lhistories):
    """The leak test: histories of 1 and 2 events stand right behind
    one of 40; without the taps' mask they would read its end."""
    packed = lencoder(lhistories)
    for j, h in enumerate(lhistories):
        np.testing.assert_allclose(lencoder([h])[0], packed[j], atol=1e-5)


def test_lfm2_padded_training_layout_agrees_with_the_packed_one(
        lweights, lencoder, lhistories):
    pp, _ = lweights
    S = LCFG.max_history
    seqs = np.full((len(lhistories), S), LCFG.vocab, np.int32)
    for r, h in enumerate(lhistories):
        seqs[r, S - len(h):] = h
    model = SeqRecModel(params=pp, n_items=LCFG.vocab,
                        backbone=bb.config_dict(LCFG))
    np.testing.assert_allclose(seqrec_encode(model, seqs),
                               lencoder(lhistories), atol=1e-5)


def test_lfm2_layerwise_reference_is_the_reference(lweights, lhistories):
    _, rp = lweights
    layerwise = lref.forward_layerwise(
        LDOC, lfm2_datagen.layer_stream(LDOC, SEED), lhistories)
    for row, h in zip(layerwise, lhistories):
        np.testing.assert_allclose(row, lref.forward(LDOC, rp, h),
                                   atol=2e-5)


@pytest.mark.parametrize("fault", sorted(lref.FAULTS))
def test_lfm2_reference_faults_move_the_logits(lweights, lhistories, fault):
    _, rp = lweights
    h = lhistories[0]
    moved = lref.forward(LDOC, rp, h, **{fault: True}) \
        - lref.forward(LDOC, rp, h)
    assert np.abs(moved).max() > 1e-2


def test_lfm2_bfloat16_path_inside_its_tolerance_and_the_control_outside(
        lhistories):
    """As `lfm2_control.py` on the chip, at the toy size and under the
    rehearsal cell's limits (at hidden 64 rounding alone passes the
    cell's limits on the widest readings): the program in bfloat16 and
    the reference with bfloat16 operands come out correct, float8
    operands not."""
    import reference
    import seq_control
    limits = json.loads((ROOT / "benchmark" / "workloads"
                         / "rehearse-lfm2-serve.json").read_text())[
                             "correct"]["limits"]
    rp = lfm2_datagen.reference_params(LDOC, SEED)
    truth = np.stack([lref.forward(LDOC, rp, h) for h in lhistories])
    pp = lfm2_datagen.program_params(LDOC, SEED)            # bfloat16
    program = _llogits(pp, _lencoder(pp)(lhistories))
    with lref.operands("bf16"):
        stated = np.stack([lref.forward(LDOC, rp, h) for h in lhistories])
    with lref.operands("fp8"):
        control = np.stack([lref.forward(LDOC, rp, h) for h in lhistories])
    for got in (program, stated):
        assert reference.verdict(seq_control.numbers(got, truth, 10),
                                 limits)[0]
    n = seq_control.numbers(control, truth, 10)
    assert not reference.verdict(n, limits)[0]
    assert n["score_err_median"] > 3 * limits["score_err_median"]


def test_mimo_file_still_gives_todays_config_field_for_field():
    """Written out from the parent commit's `config_from_json`; the
    fields this family brought read their defaults."""
    cfg = bb.load_config(str(CONFIGS / "mimo-v2.5-ep16-7l.json"))
    assert bb.config_dict(cfg) == {
        "name": "mimo-v2.5-ep16-7l", "hidden": 4096, "vocab": 19072,
        "layers": (("attn_full", "ffn_dense"), ("attn_full", "ffn_moe"))
        + (("attn_window", "ffn_moe"),) * 5,
        "n_heads": 64, "kv_heads_full": 4, "kv_heads_window": 8,
        "qk_dim": 192, "v_dim": 128, "norm": "rms", "eps": 1e-05,
        "act": "silu", "dense_width": 16384, "window": 128,
        "sink_window": True, "sink_full": False, "rotary_dim": 64,
        "rope_theta_full": 10000000.0, "rope_theta_window": 10000.0,
        "value_scale": 0.707, "positions": 0, "embed_scale": 1.0,
        "tied": False, "pad_row": False, "expert_width": 2048,
        "n_experts": 256, "top_k": 8, "norm_topk_prob": True,
        "routed_scale": 1.0, "expert_first": 0, "experts_held": 16,
        "qk_norm": False, "conv_kernel": 0, "route_eps": 0.0,
        **SSM_DEFAULTS,
        "max_history": 2048, "max_batch_tokens": 8192,
        "token_buckets": (512, 1024, 2048, 4096, 8192)}


# the fields the third family brought, as the first two read them
SSM_DEFAULTS = {"conv_bias": False, "ssm_heads": 0, "ssm_head_dim": 0,
                "ssm_groups": 0, "ssm_state": 0, "ssm_chunk": 0,
                "shared_width": 0}


def test_lfm2_file_still_gives_todays_config_field_for_field():
    """Written out from the parent commit's `config_from_json` (PR 34),
    before its chains of keys became `_FAMILIES`."""
    cfg = bb.load_config(str(CONFIGS / "lfm2-8b-a1b-pp2-12l.json"))
    conv, attn = ("conv", "ffn_moe"), ("attn_full", "ffn_moe")
    assert bb.config_dict(cfg) == {
        "name": "lfm2-8b-a1b-pp2-12l", "hidden": 2048, "vocab": 65536,
        "layers": (("conv", "ffn_dense"),) * 2 + (
            attn, conv, conv, conv, attn, conv, conv, conv, attn, conv),
        "n_heads": 32, "kv_heads_full": 8, "kv_heads_window": 8,
        "qk_dim": 64, "v_dim": 64, "norm": "rms", "eps": 1e-05,
        "act": "silu", "dense_width": 7168, "window": 0,
        "sink_window": False, "sink_full": False, "rotary_dim": 64,
        "rope_theta_full": 1000000.0, "rope_theta_window": 1000000.0,
        "value_scale": 1.0, "positions": 0, "embed_scale": 1.0,
        "tied": True, "pad_row": False, "expert_width": 1792,
        "n_experts": 32, "top_k": 4, "norm_topk_prob": True,
        "routed_scale": 1.0, "expert_first": 0, "experts_held": 32,
        "qk_norm": True, "conv_kernel": 3, "route_eps": 1e-06,
        **SSM_DEFAULTS,
        "max_history": 2048, "max_batch_tokens": 8192,
        "token_buckets": (512, 1024, 2048, 4096, 8192)}


def test_lfm2_published_widths_and_the_cut():
    doc = json.loads((CONFIGS / "lfm2-8b-a1b-pp2-12l.json").read_text())
    cfg = bb.load_config(str(CONFIGS / "lfm2-8b-a1b-pp2-12l.json"))
    assert (cfg.hidden, cfg.n_heads, cfg.kv_heads_full, cfg.qk_dim,
            cfg.v_dim, cfg.rotary_dim, cfg.conv_kernel, cfg.qk_norm) == (
                2048, 32, 8, 64, 64, 64, 3, True)
    assert (cfg.n_experts, cfg.experts_held, cfg.top_k, cfg.expert_width,
            cfg.dense_width, cfg.vocab, cfg.tied) == (
                32, 32, 4, 1792, 7168, 65536, True)
    conv, attn = ("conv", "ffn_moe"), ("attn_full", "ffn_moe")
    assert cfg.layers == (("conv", "ffn_dense"),) * 2 + (
        attn, conv, conv, conv, attn, conv, conv, conv, attn, conv)
    assert bb.n_params(cfg) == 3_928_728_256
    assert len(doc["layer_types"]) == doc["published"][
        "num_hidden_layers"] == 24
    assert set(doc["reduced"]) == {"num_hidden_layers", "n_users"}
    assert bb.config_of(json.loads(json.dumps(bb.config_dict(cfg)))) == cfg


@pytest.mark.parametrize("bad", ["sliding_attention", "mamba"])
def test_an_unknown_layer_type_is_refused(bad):
    doc = dict(LDOC, layer_types=["conv", bad] + LDOC["layer_types"][2:])
    with pytest.raises(ValueError, match=bad):
        bb.config_from_json(doc, "bad")


def test_seqrec_train_runs_at_tiny_lfm2():
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
                     backbone=str(CONFIGS / "tiny-lfm2.json"), losses=losses)
    assert len(losses) == 12 and np.isfinite(losses).all()
    assert np.mean(losses[-3:]) < np.mean(losses[:3])
    assert m.config.vocab == n_items and m.item_emb.shape == (n_items, 64)
    init = bb.init_params(jax.random.PRNGKey(0), m.config)
    assert np.abs(m.params["l0"]["conv"]["kernel"]
                  - np.asarray(init["l0"]["conv"]["kernel"])).max() > 0
    np.testing.assert_array_equal(m.params["l2"]["ffn"]["bias"],
                                  init["l2"]["ffn"]["bias"])


# -- the third family's keys (its blocks: tests/test_backbone_ssm.py) --------

NDOC = json.loads((ROOT / "benchmark" / "configs" / "tiny-nemotron.json")
                  .read_text())


def test_nemotron_published_widths_and_the_cut():
    path = CONFIGS / "nemotron-tt-30b-a3b-ep2-13l.json"
    doc = json.loads(path.read_text())
    cfg = bb.load_config(str(path))
    assert (cfg.hidden, cfg.n_heads, cfg.kv_heads_full, cfg.qk_dim,
            cfg.v_dim, cfg.rotary_dim, cfg.positions, cfg.qk_norm) == (
                2688, 32, 2, 128, 128, 0, 0, False)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state,
            cfg.ssm_chunk, cfg.conv_kernel, cfg.conv_bias) == (
                64, 64, 8, 128, 128, 4, True)
    assert (cfg.n_experts, cfg.experts_held, cfg.expert_first, cfg.top_k,
            cfg.expert_width, cfg.shared_width, cfg.act, cfg.routed_scale,
            cfg.route_eps, cfg.vocab, cfg.tied, cfg.eps) == (
                128, 64, 0, 6, 1856, 3712, "relu2", 2.5, 1e-20, 65536,
                False, 1e-5)
    assert "".join({"ssm": "M", "ffn_moe": "E", "attn_full": "*"}[b]
                   for (b,) in cfg.layers) == "MEMEM*EMEMEM*" \
        == doc["hybrid_override_pattern"][:13]
    assert bb.n_params(cfg) == 3_926_018_560
    assert len(doc["hybrid_override_pattern"]) == doc["published"][
        "num_hidden_layers"] == 52
    assert (doc["published"]["n_routed_experts"],
            doc["published"]["vocab_size"]) == (128, 131072)
    assert set(doc["reduced"]) == {"num_hidden_layers", "n_routed_experts",
                                   "vocab_size", "n_users"}
    assert all(b % cfg.ssm_chunk == 0 for b in cfg.token_buckets)
    assert bb.config_of(json.loads(json.dumps(bb.config_dict(cfg)))) == cfg


@pytest.mark.parametrize("bad", ["-", "X"])
def test_a_pattern_letter_outside_m_e_star_is_refused(bad):
    doc = dict(NDOC, hybrid_override_pattern="ME" + bad + "*EM*E")
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        bb.config_from_json(doc, "bad")


def test_a_file_with_two_families_patterns_is_refused():
    with pytest.raises(ValueError, match="one layer pattern"):
        bb.config_from_json(dict(NDOC, layer_types=["conv"] * 8), "bad")
    with pytest.raises(ValueError, match="one layer pattern"):
        bb.config_from_json({k: v for k, v in NDOC.items()
                             if k != "hybrid_override_pattern"}, "bad")
