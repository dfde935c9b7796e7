"""The Nemotron-H cell's own files: the operation counts against the
issue's reckoning, the weights, the control and the seven faults at the
toy size, the rehearsal cell through `run.py`, and where the cell is
listed."""

import json

import pytest

import harness
import nemotron_control
import nemotron_datagen
import nemotron_opcount
import nemotron_reference
import run as bench_run
import seq_readers
from manifest import Manifest

CONFIGS = harness.BENCH_DIR / "configs"
CELL = "nemotron-tt-hist-c32"
CONFIG = "nemotron-tt-30b-a3b-ep2-13l"


@pytest.fixture(scope="module")
def arch():
    return nemotron_reference.arch(
        harness.load_json(CONFIGS / f"{CONFIG}.json"))


def test_parameters_and_flops_a_token_are_the_issues_reckoning(arch):
    oc = nemotron_opcount
    assert oc.block_params(arch, "mamba") == 38_744_896
    assert oc.block_params(arch, "attn") == 23_399_040
    assert oc.block_params(arch, "moe") == 658_885_376
    assert oc._expert_params(arch) == 9_977_856
    assert oc._shared_params(arch) == 19_955_712
    assert oc.stack_params(arch) == 3_926_018_560
    assert oc.stack_params(arch) * 2 / 1e9 == pytest.approx(7.85, abs=0.005)
    assert oc.n_layers(arch) == {"mamba": 6, "attn": 2, "moe": 5}
    # 530M active parameters a token: 38.7M x 6 + 23.4M x 2 + 50.2M x 5
    assert oc.token_matmul_flops(arch) / 2e6 == pytest.approx(530.2, abs=0.1)
    assert oc.uniform_pairs(arch, 1.0) == 15.0             # 3 a layer
    assert oc.scan_flops_a_token(arch) == 262_144 + 1_048_576 + 2_097_152
    whole = oc.token_matmul_flops(arch)
    assert 6 * 2 * oc.mamba_matrix_params(arch) / whole \
        == pytest.approx(0.44, abs=0.005)
    assert 6 * oc.scan_flops_a_token(arch) / whole < 0.05
    assert oc.pair_flops(arch) == 2 * 32 * 2 * 128
    assert oc.serve_flops(arch, [1], 0.0) - oc.token_dense_flops(arch) \
        - 2 * oc.pair_flops(arch) == 2.0 * 65536 * 2688    # the head's row


def test_the_kernels_work_counts_two_products_a_pair_and_the_scan(arch):
    oc = nemotron_opcount
    flops, bytes_ = oc.moe_work(arch, 84_000, calls=1.0)
    assert flops == 84_000 * 2 * 2 * 2688 * 1856
    assert bytes_ == 5 * 64 * 2 * 2688 * 1856 * 2 + 84_000 * 2 * 2688 * 2
    flops, bytes_ = oc.ssm_work(arch, 5600, calls=1.0)
    assert flops == 5600 * 6 * 3_407_872
    assert bytes_ == 6 * (5600 * (2 * 6144 + 4 * 64 + 2 * 4096) + 4 * 64)
    full, _ = oc.attention_work(arch, [300, 1])
    assert full == 16384 * 2 * (300 * 301 // 2 + 1)
    assert oc.attention_work(arch, [10])[1] == 10 * 2 * 2 * 256 * (32 + 2)


def test_ssm_roofline_reads_the_drivers_work_and_nothing_without_it():
    class Trace:
        ops = {"ssm_chunk_scan.3": 0.02, "ssm_chunk_scan.9": 0.02,
               "moe_grouped_matmul.1": 1.0}

        def module_seconds(self, match):
            return [0.1, 0.1] if match == "^jit_seq_stack" else []

    spec = harness.load_json(harness.BENCH_DIR / "layer_metrics"
                             / "seq_ssm_roofline.json")
    assert spec["reader"] == "seq_readers.kernel_roofline"
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    facts = {"trace": Trace(), "peaks": peaks,
             "seq": {"calls": 4, "ssm": (4 * 197e12 * 1e-3, 1.0)}}
    # 2 of 4 calls traced: 2 ms of required work over 40 ms of kernel
    assert seq_readers.kernel_roofline(facts, **spec["args"]) \
        == pytest.approx(5.0)
    assert facts["bounds"]["ssm"] == "flops"
    del facts["seq"]["ssm"]           # the parent's driver counts none
    assert seq_readers.kernel_roofline(facts, **spec["args"]) is None
    assert seq_readers.kernel_roofline({"trace": None}, **spec["args"]) \
        is None


def test_weights_are_bfloat16_values_and_the_same_for_both_sides():
    import jax
    import jax.numpy as jnp
    doc = harness.load_json(CONFIGS / "tiny-nemotron.json")
    big = 3_000_000_019                    # more than 32 signed bits hold
    rp = nemotron_datagen.reference_params(doc, big)
    pp = nemotron_datagen.program_params(doc, big)
    for ref_w, prog_w, std in (
            (rp["l1"]["w_up"], pp["l1"]["ffn"]["w_up"], 1 / 8),
            (rp["l1"]["shared_down"], pp["l1"]["ffn"]["shared"]["w_down"],
             1 / 8),
            (rp["l0"]["w_in"], pp["l0"]["ssm"]["w_in"], 1 / 8),
            (rp["l0"]["kernel"], pp["l0"]["ssm"]["kernel"], 1 / 2),
            (rp["head"], pp["head"], 1 / 8)):
        assert ref_w.dtype == jnp.float32 and prog_w.dtype == jnp.bfloat16
        assert (ref_w.astype(jnp.bfloat16).astype(jnp.float32)
                == ref_w).all()
        assert (prog_w.astype(jnp.float32) == ref_w).all()
        assert float(jnp.std(ref_w)) == pytest.approx(std, rel=0.2)
    assert not bool((rp["head"] == rp["embed"]).all())      # untied
    for name in ("conv_bias", "dt_bias", "a_log", "d"):
        assert pp["l0"]["ssm"][name].dtype == jnp.float32
        assert (pp["l0"]["ssm"][name] == rp["l0"][name]).all()
    assert (rp["l0"]["d"] == 1).all() and (rp["l0"]["norm_g"] == 1).all()
    rates = jnp.exp(rp["l0"]["a_log"])
    steps = jax.nn.softplus(rp["l0"]["dt_bias"])
    assert bool(((1 <= rates) & (rates <= 16)).all())
    assert bool(((0.9e-3 <= steps) & (steps <= 0.11)).all())
    assert 0.1 < float(jnp.std(rp["l0"]["conv_bias"])) < 0.5
    assert 0.003 < float(jnp.std(rp["l1"]["bias"])) < 0.03
    other = nemotron_datagen.reference_params(doc, big + 1)
    assert not bool((other["l3"]["wq"] == rp["l3"]["wq"]).all())
    assert all(bool(jnp.isfinite(x.astype(jnp.float32)).all())
               for x in jax.tree_util.tree_leaves(pp))


def test_control_and_faults_read_as_not_correct_at_the_toy_size():
    cell = harness.load_json(harness.BENCH_DIR / "workloads"
                             / "rehearse-nemotron-serve.json")
    cfg_file = CONFIGS / "tiny-nemotron.json"
    # as the LFM2 cell's test: at hidden 64 bfloat16's rounding alone
    # passes the cell's limits on the widest numbers, so the toy size is
    # held to the rehearsal's median
    limits = cell["correct"]["limits"]
    got = nemotron_control.readings(cell, harness.load_json(cfg_file),
                                    str(cfg_file), 13, 10)
    faults = {f"fault_{name}" for name in nemotron_reference.FAULTS}
    assert len(faults) == 7
    assert set(got) == {"program", "stated_bf16", "control_fp8"} | faults
    assert nemotron_control.failures(got, limits) == [], got
    worst = max(got[name]["score_err_median"]
                for name in nemotron_control.MUST_PASS)
    assert worst < limits["score_err_median"] / 1.5
    # with limits this loose every control and fault passes, and that
    # is an exit code
    loose = {name: 10.0 for name in limits}
    assert sorted(nemotron_control.failures(got, loose)) \
        == sorted(faults | {"control_fp8"})


def test_rehearsal_cell_runs_whole_and_prints_the_new_metrics():
    manifest = Manifest.load(queued=True)
    out = bench_run.run_cell(manifest, "rehearse-nemotron-serve",
                             3_000_000_019, 6.0, True, rehearse_cpu=True)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 20
    got = set(out["metrics"])
    assert {"seq_ssm_reset_chunk_pct", "seq_unrouted_pct",
            "seq_moe_buffers_mean", "seq_moe_gather_combine_pct",
            "seq_call_tokens_mean", "seq_pad_pct", "seq_history_ms_mean",
            "seq_expert_load_max_over_mean", "serve_batch_rows_mean",
            "batch_cycle_ms_mean", "batch_inflight_mean"} <= got \
        <= {x["name"] for x in manifest.per_layer(CELL)}
    # half the experts held: every pair of a call fits its one buffer,
    # and the rows come back by the gather
    assert out["metrics"]["seq_moe_buffers_mean"]["value"] == 1.0
    assert out["metrics"]["seq_moe_gather_combine_pct"]["value"] == 100.0
    assert 0 < out["metrics"]["seq_ssm_reset_chunk_pct"]["value"] <= 100
    # the kernels' shares are device times: never on a CPU
    assert not [n for n in got if "roofline" in n or "mfu" in n
                or "device" in n or "share" in n]
    assert set(out["compared"]) == {"rank_gap", "score_err",
                                    "score_err_median", "banned_served",
                                    "short_replies"}
    json.dumps(out)


def test_the_cell_is_listed_where_the_issue_says():
    m = Manifest.load()
    assert {x["name"] for x in m.end_to_end(CELL)} == {
        "serve_qps", "serve_p50_ms", "setup_s"}
    mine = {x["name"] for x in m.per_layer(CELL)}
    assert {x["name"] for x in m.per_layer("lfm2-hist-c32")} \
        | {"seq_unrouted_pct", "seq_ssm_roofline",
           "seq_ssm_kernel_share_pct", "seq_ssm_reset_chunk_pct"} <= mine
    new = {x["name"]: x for x in m.doc["per_layer"]
           if x["name"].startswith("seq_ssm_")}
    assert len(new) == 3 and all(
        x["workloads"] == [CELL] and x["moves"] == "serve_qps"
        for x in new.values())
    assert (new["seq_ssm_roofline"]["layer"],
            new["seq_ssm_kernel_share_pct"]["layer"],
            new["seq_ssm_reset_chunk_pct"]["layer"]) == (
                "kernels", "state-space mixer", "state-space mixer")
    cell = m.cells[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "closed-c32-hist", 1)
    assert len(cell["why"]) <= 200
    cfg = harness.load_json(CONFIGS / f"{CONFIG}.json")
    assert set(m.configs[CONFIG]["reduced"]) == set(cfg["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size", "n_users"}
    assert m.configs[CONFIG]["source"] == cfg["source"]
    # the traffic is lfm2-hist-c32's parameter for parameter
    mine = harness.load_json(harness.BENCH_DIR / "workloads"
                             / f"{CELL}.json")
    lfm2 = harness.load_json(harness.BENCH_DIR / "workloads"
                             / "lfm2-hist-c32.json")
    assert mine["traffic"] == lfm2["traffic"]
    assert mine["traffic"]["n_items"] == cfg["vocab_size"] == 65536
    assert (mine["warm_bursts"], mine["trace"]) == (lfm2["warm_bursts"],
                                                    lfm2["trace"])
    assert mine["driver"] == "nemotron_http_closed"


def test_every_published_number_of_the_catalog_row_is_in_the_file():
    """The catalog row's `config`, key for key, but for the three that
    `reduced` lists."""
    cfg = harness.load_json(CONFIGS / f"{CONFIG}.json")
    published = {
        "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128,
        "hidden_size": 2688, "intermediate_size": 1856,
        "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
        "mamba_num_heads": 64, "max_position_embeddings": 262144,
        "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
        "n_groups": 8, "n_shared_experts": 1, "norm_eps": 1e-05,
        "num_attention_heads": 32, "num_experts_per_tok": 6,
        "num_key_value_heads": 2, "num_logits_to_keep": 1,
        "partial_rotary_factor": 1, "rope_theta": 10000,
        "routed_scaling_factor": 2.5, "ssm_state_size": 128,
        "time_step_floor": 0.0001, "time_step_max": 0.1,
        "time_step_min": 0.001, "topk_group": 1}
    assert {k: cfg[k] for k in published} == published
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (13, 64, 65536)
    assert cfg["published"]["num_hidden_layers"] == 52 == len(
        cfg["hybrid_override_pattern"])
    assert "-" not in cfg["hybrid_override_pattern"]
