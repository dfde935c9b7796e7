"""The LFM2 cell's own files: the operation counts against the issue's
table, the weights, the control and the four faults at the toy size,
the new reader, the rehearsal cell through `run.py`, and where the
cell is listed."""

import json

import pytest

import harness
import lfm2_control
import lfm2_datagen
import lfm2_opcount
import lfm2_readers
import lfm2_reference
import run as bench_run
from manifest import Manifest

CONFIGS = harness.BENCH_DIR / "configs"
CELL = "lfm2-hist-c32"


@pytest.fixture(scope="module")
def arch():
    return lfm2_reference.arch(
        harness.load_json(CONFIGS / "lfm2-8b-a1b-pp2-12l.json"))


def test_parameters_and_flops_a_token_are_the_issues_table(arch):
    assert lfm2_opcount.mixer_params(arch, "conv") + 3 * 2048 == 16_783_360
    assert lfm2_opcount.mixer_params(arch, "attn") + 2 * 64 == 10_485_888
    # mixers 182,507,904 + norms 49,152 + dense 88,080,384
    # + experts 3,523,871,040 + table 134,219,776
    assert lfm2_opcount.stack_params(arch) == 3_928_728_256
    assert lfm2_opcount.stack_params(arch) * 2 / 1e9 \
        == pytest.approx(7.86, abs=0.005)
    assert lfm2_opcount.n_layers(arch) == {"conv": 9, "attn": 3, "moe": 10}
    assert lfm2_opcount.token_matmul_flops(arch) / 1e6 \
        == pytest.approx(1423.2, abs=0.05)
    experts = lfm2_opcount.uniform_pairs(arch, 1.0) \
        * lfm2_opcount.pair_expert_flops(arch)
    assert experts / 1e6 == pytest.approx(880.8, abs=0.05)
    assert experts / lfm2_opcount.token_matmul_flops(arch) \
        == pytest.approx(0.62, abs=0.005)
    assert lfm2_opcount.pair_flops(arch) == 8192
    assert lfm2_opcount.serve_flops(arch, [1], 0.0) \
        - lfm2_opcount.token_dense_flops(arch) - 3 * 8192 \
        == 2.0 * 65536 * 2048                              # the head's row


def test_one_expert_layer_of_a_5500_token_call_is_the_issues(arch):
    flops, bytes_ = lfm2_opcount.moe_work(arch, 22_000, calls=0.1)
    assert flops == pytest.approx(484.4e9, rel=1e-3)
    assert bytes_ - 22_000 * 2 * 2048 * 2 == pytest.approx(704.6e6, rel=1e-3)
    full, _ = lfm2_opcount.attention_work(arch, [300, 1])
    assert full == 8192 * 3 * (300 * 301 // 2 + 1)
    assert lfm2_opcount.attention_work(arch, [10])[1] \
        == 10 * 3 * 2 * 128 * (32 + 8)


def test_weights_are_bfloat16_values_and_the_same_for_both_sides():
    import jax
    import jax.numpy as jnp
    doc = harness.load_json(CONFIGS / "tiny-lfm2.json")
    big = 3_000_000_019                    # more than 32 signed bits hold
    rp = lfm2_datagen.reference_params(doc, big)
    pp = lfm2_datagen.program_params(doc, big)
    for ref_w, prog_w, std in (
            (rp["l2"]["w_gate_up"], pp["l2"]["ffn"]["w_gate_up"], 1 / 8),
            (rp["l0"]["w_in"], pp["l0"]["conv"]["w_in"], 1 / 8),
            (rp["l0"]["kernel"], pp["l0"]["conv"]["kernel"], 3 ** -0.5),
            (rp["embed"], pp["embed"], 1 / 8)):
        assert ref_w.dtype == jnp.float32 and prog_w.dtype == jnp.bfloat16
        assert (ref_w.astype(jnp.bfloat16).astype(jnp.float32)
                == ref_w).all()
        assert (prog_w.astype(jnp.float32) == ref_w).all()
        assert float(jnp.std(ref_w)) == pytest.approx(std, rel=0.15)
    assert "head" not in pp and "head" not in rp            # tied
    assert (rp["l2"]["norm_q"] == 1).all()
    assert (pp["l2"]["attn"]["k_norm"]["g"] == 1).all()
    assert 0.003 < float(jnp.std(rp["l2"]["bias"])) < 0.03
    other = lfm2_datagen.reference_params(doc, big + 1)
    assert not bool((other["l2"]["wq"] == rp["l2"]["wq"]).all())
    assert all(bool(jnp.isfinite(x.astype(jnp.float32)).all())
               for x in jax.tree_util.tree_leaves(pp))


def test_control_and_faults_read_as_not_correct_at_the_toy_size():
    cell = harness.load_json(harness.BENCH_DIR / "workloads"
                             / "rehearse-lfm2-serve.json")
    cfg_file = CONFIGS / "tiny-lfm2.json"
    # the rehearsal's limits: at hidden 64 bfloat16's rounding alone
    # reads 0.08-0.16 on the widest numbers, over the cell's limits, so
    # the toy size is held to the median, which tells every one apart
    # (program 0.010, the nearest fault, the expert bias left out of a
    # selection of 2 from 8, 0.033: CPU, this seed)
    limits = cell["correct"]["limits"]
    got = lfm2_control.readings(cell, harness.load_json(cfg_file),
                                str(cfg_file), 13, 10)
    faults = {f"fault_{name}" for name in lfm2_reference.FAULTS}
    assert len(faults) == 4
    assert set(got) == {"program", "stated_bf16", "control_fp8"} | faults
    assert lfm2_control.failures(got, limits) == [], got
    worst = max(got[name]["score_err_median"]
                for name in lfm2_control.MUST_PASS)
    assert worst < limits["score_err_median"] / 1.5
    for name in set(got) - set(lfm2_control.MUST_PASS):
        assert got[name]["score_err_median"] \
            > 1.5 * limits["score_err_median"], (name, got[name])
    # with limits this loose every control and fault passes, and that
    # is an exit code
    loose = {name: 10.0 for name in limits}
    assert sorted(lfm2_control.failures(got, loose)) \
        == sorted(faults | {"control_fp8"})


def test_the_control_takes_the_longest_and_the_shortest_history():
    cell = harness.load_json(harness.BENCH_DIR / "workloads"
                             / "rehearse-lfm2-serve.json")
    cfg = harness.load_json(CONFIGS / "tiny-lfm2.json")
    lens = sorted(len(h) for h in lfm2_control.histories_of(cell, cfg, 3, 6))
    assert len(lens) == 6 and lens[0] == 2 and lens[-1] == 48


def test_kernel_share_reads_the_trace_and_nothing_where_there_is_none():
    class Trace:
        ops = {"moe_grouped_matmul.3": 0.5, "moe_grouped_matmul.9": 0.25,
               "packed_attention.1": 1.0, "fusion.7": 5.0}

        def module_seconds(self, match):
            return [1.0, 2.0] if match == "^jit_seq_stack" else []

    args = {"ops": "^moe_grouped_matmul", "module": "^jit_seq_stack"}
    assert lfm2_readers.kernel_share({"trace": Trace()}, **args) \
        == pytest.approx(25.0)
    assert lfm2_readers.kernel_share({"trace": None}, **args) is None
    assert lfm2_readers.kernel_share({}, **args) is None
    # a program without the kernel, and a trace without the stack
    assert lfm2_readers.kernel_share(
        {"trace": Trace()}, ops="^no_such_kernel",
        module="^jit_seq_stack") is None
    assert lfm2_readers.kernel_share(
        {"trace": Trace()}, ops="^moe_grouped_matmul",
        module="^jit_other") is None


def test_rehearsal_cell_runs_whole_and_prints_the_new_metrics():
    manifest = Manifest.load(queued=True)
    out = bench_run.run_cell(manifest, "rehearse-lfm2-serve", 3_000_000_019,
                             6.0, True, rehearse_cpu=True)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 20
    got = set(out["metrics"])
    assert {"seq_moe_buffers_mean", "seq_call_tokens_mean", "seq_pad_pct",
            "seq_history_ms_mean", "seq_expert_load_max_over_mean",
            "serve_batch_rows_mean", "batch_cycle_ms_mean",
            "batch_inflight_mean"} <= got \
        <= {x["name"] for x in manifest.per_layer(CELL)}
    # every expert held: every pair of a call fits its one buffer
    assert out["metrics"]["seq_moe_buffers_mean"]["value"] == 1.0
    assert "seq_unrouted_pct" not in got
    # the kernel's share of the stack is a device time: never on a CPU
    assert not [n for n in got if "roofline" in n or "mfu" in n
                or "device" in n or "share" in n]
    assert set(out["compared"]) == {"rank_gap", "score_err",
                                    "score_err_median", "banned_served",
                                    "short_replies"}
    json.dumps(out)


def test_the_cell_is_listed_where_the_issue_says_and_nowhere_else():
    m = Manifest.load()
    assert {x["name"] for x in m.end_to_end(CELL)} == {
        "serve_qps", "serve_p50_ms", "setup_s"}
    assert {x["name"] for x in m.per_layer(CELL)} == {
        "serve_batch_rows_mean", "serve_server_ms_p50", "serve_tail_ms_p95",
        "topk_device_ms", "device_idle_pct.serve",
        "serve_worker_wait_ms_mean", "serve_lane_wait_ms_mean",
        "batch_cycle_ms_mean", "batch_host_ms_mean", "topk_merge_tile_pct",
        "batch_inflight_mean", "seq_serve_mfu_pct", "seq_stack_device_ms",
        "seq_attn_roofline", "seq_moe_roofline", "seq_call_tokens_mean",
        "seq_pad_pct", "seq_history_ms_mean",
        "seq_expert_load_max_over_mean", "seq_moe_kernel_share_pct",
        "seq_moe_buffers_mean"}
    new = {x["name"]: x for x in m.doc["per_layer"]
           if x["name"] in ("seq_moe_kernel_share_pct",
                            "seq_moe_buffers_mean")}
    assert all(set(x["workloads"]) == {CELL, "mimo25-hist-c32"}
               and x["moves"] == "serve_qps" and x["layer"] == "expert layer"
               for x in new.values()) and len(new) == 2
    cell = m.cells[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lfm2-8b-a1b-pp2-12l", "closed-c32-hist", 1)
    cfg = harness.load_json(CONFIGS / "lfm2-8b-a1b-pp2-12l.json")
    assert set(m.configs["lfm2-8b-a1b-pp2-12l"]["reduced"]) == set(
        cfg["reduced"]) == {"num_hidden_layers", "n_users"}
    assert m.configs["lfm2-8b-a1b-pp2-12l"]["source"] == cfg["source"]
    # the traffic is mimo25-hist-c32's but for the catalog
    mine = harness.load_json(harness.BENCH_DIR / "workloads"
                             / f"{CELL}.json")
    mimo = harness.load_json(harness.BENCH_DIR / "workloads"
                             / "mimo25-hist-c32.json")
    assert {**mine["traffic"], "n_items": 19072} == mimo["traffic"]
    assert mine["traffic"]["n_items"] == cfg["vocab_size"] == 65536
    assert (mine["warm_bursts"], mine["trace"]) == (mimo["warm_bursts"],
                                                    mimo["trace"])
