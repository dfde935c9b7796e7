"""The sequence cell's own files: the operation counts against the
issue's table, the readers with nothing to read, the control and the
faults at the toy size, and the rehearsal cell through `run.py`."""

import json

import numpy as np
import pytest

import harness
import run as bench_run
import seq_control
import seq_datagen
import seq_opcount
import seq_readers
import seq_reference
from manifest import Manifest

CONFIGS = harness.BENCH_DIR / "configs"


@pytest.fixture(scope="module")
def arch():
    return seq_reference.arch(
        harness.load_json(CONFIGS / "mimo-v2.5-ep16-7l.json"))


def test_parameters_and_flops_a_token_are_the_issues_table(arch):
    # 290.5M + 492.8M + 5 x 498.1M + 156.2M = 3,429.9M parameters
    assert seq_opcount.stack_params(arch) == 3_429_955_392
    assert seq_opcount.stack_params(arch) * 2 / 2**30 \
        == pytest.approx(6.39, abs=0.01)                   # GiB in bf16
    assert seq_opcount.token_matmul_flops(arch) / 1e9 \
        == pytest.approx(1.87, abs=0.005)
    assert seq_opcount.expert_visits(arch) == 0.5          # 8 x 16 / 256
    assert seq_opcount.pair_flops(arch) == 40_960          # "41 kFLOP"


def test_pairs_respect_the_window_and_the_history(arch):
    assert seq_opcount.history_pairs(arch, 1) == (1, 1)
    assert seq_opcount.history_pairs(arch, 128) == (128 * 129 // 2,) * 2
    full, window = seq_opcount.history_pairs(arch, 300)
    assert full == 300 * 301 // 2
    assert window == 128 * 129 // 2 + 172 * 128
    flops, bytes_ = seq_opcount.attention_work(arch, [300, 1])
    assert flops == 40_960 * (2 * (full + 1) + 5 * (window + 1))
    # a token and layer: q and o of 64 heads, k and v of 4 or 8, 2 bytes
    assert bytes_ == 301 * 2 * (2 * (64 + 4) * 320 + 5 * (64 + 8) * 320)


def test_expert_work_counts_pairs_and_each_call_reads_the_weights(arch):
    per_expert = 3 * 4096 * 2048
    pairs = seq_opcount.uniform_pairs(arch, 8192)
    assert pairs == 6 * 4096              # six expert layers, 0.5 a token
    flops, bytes_ = seq_opcount.moe_work(arch, pairs, calls=1)
    assert flops == 6 * 4096 * 2 * per_expert
    assert bytes_ == 6 * (16 * per_expert * 2 + 4096 * 2 * 4096 * 2)
    assert seq_opcount.moe_work(arch, pairs, 2)[1] - bytes_ \
        == 6 * 16 * per_expert * 2
    # the calls' own pairs, not the expectation: 1.4 times the pairs is
    # 1.4 times the FLOPs
    assert seq_opcount.moe_work(arch, 1.4 * pairs, 1)[0] \
        == pytest.approx(1.4 * flops)


def test_the_whole_steps_flops_take_the_calls_own_pairs(arch):
    lengths = [300, 1, 2048]
    tokens = sum(lengths)
    attn, _ = seq_opcount.attention_work(arch, lengths)
    head = len(lengths) * 2.0 * 19072 * 4096
    uniform = seq_opcount.serve_flops(
        arch, lengths, seq_opcount.uniform_pairs(arch, tokens))
    assert uniform == pytest.approx(
        tokens * seq_opcount.token_matmul_flops(arch) + attn + head)
    none = seq_opcount.serve_flops(arch, lengths, 0.0)
    assert none == pytest.approx(
        tokens * seq_opcount.token_dense_flops(arch) + attn + head)
    assert uniform - none == pytest.approx(
        tokens * 3 * seq_opcount.pair_expert_flops(arch))


def test_the_uniform_unrouted_share_is_the_issues(arch):
    p = 1.0
    for i in range(arch["top_k"]):
        p *= (arch["E"] - arch["held"] - i) / (arch["E"] - i)
    assert p == pytest.approx(0.592, abs=0.001)


def test_history_lengths_are_the_cells_and_every_seeds():
    cell = harness.load_json(harness.BENCH_DIR / "workloads"
                             / "mimo25-hist-c32.json")
    h = cell["traffic"]["history"]
    n = seq_datagen.history_lengths(4096, median=h["median"],
                                    sigma=h["sigma"], lo=h["min"],
                                    hi=h["max"])
    assert n[:5].tolist() == [155, 1096, 287, 96, 556]
    assert int(n.sum()) == 1_643_900 and n.min() == 16 and n.max() == 2048
    assert round(float(n.mean())) == 401
    assert (n <= 128).mean() == pytest.approx(0.25, abs=0.01)
    assert (n > 1024).mean() == pytest.approx(0.083, abs=0.002)
    assert (n == 2048).mean() == pytest.approx(0.019, abs=0.002)
    a = seq_datagen.histories(n[:50], 19072, 1.1, 7)
    assert (a == seq_datagen.histories(n[:50], 19072, 1.1, 7)).all()
    assert (a != seq_datagen.histories(n[:50], 19072, 1.1, 8)).any()
    assert a.min() >= 0 and a.max() < 19072


def test_the_generator_is_given_the_runs_seed_and_no_other():
    """The seed alone decides the order of requests (ISSUE.md): the
    cells' traffic has no key that picks among streams."""
    import loadgen
    for name in ("mimo25-hist-c32", "rehearse-seq-serve"):
        traffic = harness.load_json(harness.BENCH_DIR / "workloads"
                                    / f"{name}.json")["traffic"]
        assert "level" not in traffic
        a, b = (loadgen.RequestStream(traffic, 2_147_485_108)
                for _ in range(2))
        assert [a.get(i) for i in range(64)] == [b.get(i) for i in range(64)]


def test_weights_are_bfloat16_values_and_the_same_for_both_sides():
    import jax
    import jax.numpy as jnp
    doc = harness.load_json(CONFIGS / "tiny-mimo.json")
    big = 3_000_000_019                    # more than 32 signed bits hold
    rp = seq_datagen.reference_params(doc, big)
    pp = seq_datagen.program_params(doc, big)
    w = rp["l1"]["w_gate_up"]
    assert w.dtype == jnp.float32
    assert (w.astype(jnp.bfloat16).astype(jnp.float32) == w).all()
    assert (pp["l1"]["ffn"]["w_gate_up"].astype(jnp.float32) == w).all()
    assert pp["l1"]["ffn"]["w_gate_up"].dtype == jnp.bfloat16
    assert float(jnp.std(w)) == pytest.approx(1 / 8, rel=0.05)  # 1/sqrt(64)
    assert float(jnp.abs(rp["l2"]["sink"]).max()) > 0.1
    other = seq_datagen.reference_params(doc, big + 1)
    assert not bool((other["l1"]["wq"] == rp["l1"]["wq"]).all())
    leaves = jax.tree_util.tree_leaves(pp)
    assert all(bool(jnp.isfinite(x.astype(jnp.float32)).all())
               for x in leaves)


def test_seq_readers_with_nothing_to_read_return_nothing():
    facts = {"config": {}, "hist": {}, "trace": None}
    assert seq_readers.serve_mfu(facts) is None
    assert seq_readers.kernel_roofline(
        facts, ops="^packed_attention", work="attn",
        module="^jit_seq_stack") is None
    # a program without the stack (the parent): no seq facts either way
    facts["peaks"] = {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}
    facts["window_s"] = 30.0
    assert seq_readers.serve_mfu(facts) is None


def test_seq_readers_read_what_the_driver_counted():
    class Trace:
        ops = {"packed_attention.3": 0.25, "packed_attention.9": 0.25,
               "moe_grouped_matmul.1": 1.0, "fusion.7": 5.0}

        def module_seconds(self, match):
            return [0.1, 0.1]

    facts = {"trace": Trace(), "window_s": 10.0,
             "peaks": {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0},
             "seq": {"serve_flops": 500.0, "calls": 8,
                     "attn": (100.0, 4.0), "moe": (40.0, 20.0)}}
    # a program without the pairs counter: the driver leaves these out
    bare = {**facts, "seq": {"calls": 8, "attn": (100.0, 4.0)}}
    assert seq_readers.serve_mfu(bare) is None
    assert seq_readers.kernel_roofline(
        bare, ops="^moe_grouped_matmul", work="moe",
        module="^jit_seq_stack") is None
    assert seq_readers.serve_mfu(facts) == pytest.approx(50.0)
    # 2 of 8 calls traced: a quarter of the work; flops bound: 0.25 s
    assert seq_readers.kernel_roofline(
        facts, ops="^packed_attention", work="attn",
        module="^jit_seq_stack") == pytest.approx(100 * 0.25 / 0.5)
    # bytes bound: 20 / 4 / 10 = 0.5 s over 1 s
    assert seq_readers.kernel_roofline(
        facts, ops="^moe_grouped_matmul", work="moe",
        module="^jit_seq_stack") == pytest.approx(50.0)
    assert facts["bounds"] == {"attn": "flops", "moe": "bytes"}


def test_control_and_faults_read_as_not_correct_at_the_toy_size():
    cell = harness.load_json(harness.BENCH_DIR / "workloads"
                             / "rehearse-seq-serve.json")
    cfg_file = CONFIGS / "tiny-mimo.json"
    # the cell's own limits on the widest readings (the rehearsal's are
    # loose for the served path's bfloat16 at hidden 64), the
    # rehearsal's on the median (rounding is twice as coarse at 64)
    limits = {**harness.load_json(
        harness.BENCH_DIR / "workloads"
        / "mimo25-hist-c32.json")["correct"]["limits"],
        "score_err_median": cell["correct"]["limits"]["score_err_median"]}
    got = seq_control.readings(cell, harness.load_json(cfg_file),
                               str(cfg_file), 13, 10)
    assert set(got) == {"program", "stated_bf16", "control_fp8",
                        "fault_window_ignored", "fault_sink_dropped",
                        "fault_share_shifted"}
    assert seq_control.failures(got, limits) == [], got
    # an expert selection that flips on rounding moves few replies,
    # a fault every one: the sample's median tells them apart
    worst = max(got[name]["score_err_median"]
                for name in seq_control.MUST_PASS)
    assert worst < limits["score_err_median"] / 3
    for name in set(got) - set(seq_control.MUST_PASS):
        assert got[name]["score_err_median"] \
            > 3 * limits["score_err_median"], (name, got[name])
    # a control that passes is an exit code: with limits this loose
    # every one of them does
    loose = {name: 10.0 for name in limits}
    assert sorted(seq_control.failures(got, loose)) == [
        "control_fp8", "fault_share_shifted", "fault_sink_dropped",
        "fault_window_ignored"]


def test_rehearsal_cell_runs_whole_and_prints_the_new_metrics():
    manifest = Manifest.load(queued=True)
    out = bench_run.run_cell(manifest, "rehearse-seq-serve", 3_000_000_019,
                             6.0, True, rehearse_cpu=True)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 20
    want = {"serve_batch_rows_mean", "serve_worker_wait_ms_mean",
            "serve_lane_wait_ms_mean", "batch_cycle_ms_mean",
            "batch_host_ms_mean", "serve_server_ms_p50", "serve_tail_ms_p95",
            "seq_call_tokens_mean", "seq_pad_pct",
            "seq_history_ms_mean", "seq_expert_load_max_over_mean",
            "seq_unrouted_pct"}
    # the shares of a peak and the device's times are never read on a CPU
    assert set(out["metrics"]) == want
    assert 0 < out["metrics"]["seq_unrouted_pct"]["value"] < 100
    assert set(out["compared"]) == {"rank_gap", "score_err",
                                    "score_err_median", "banned_served",
                                    "short_replies"}
    assert all(c["limit"] is not None for c in out["compared"].values())
    json.dumps(out)


def test_the_new_cell_is_listed_where_the_issue_says():
    m = Manifest.load()
    cell = "mimo25-hist-c32"
    assert {x["name"] for x in m.end_to_end(cell)} == {
        "serve_qps", "serve_p50_ms", "setup_s"}
    per_layer = {x["name"] for x in m.per_layer(cell)}
    assert len(per_layer) == 19 and {
        "seq_serve_mfu_pct", "seq_stack_device_ms", "seq_attn_roofline",
        "seq_moe_roofline", "device_idle_pct.serve", "topk_device_ms",
        "topk_merge_tile_pct", "serve_server_ms_p50",
        "serve_tail_ms_p95"} <= per_layer
    # their required work is ALS's
    assert not per_layer & {"serve_mfu_pct", "topk_roofline"}
    cfg = harness.load_json(CONFIGS / "mimo-v2.5-ep16-7l.json")
    assert set(m.configs["mimo-v2.5-ep16-7l"]["reduced"]) == set(
        cfg["reduced"]) == {"num_hidden_layers", "n_routed_experts",
                            "vocab_size", "n_users"}
