"""The Brumby cell's own files: the operation counts against the
configuration's sizing, the weights and their gates, the control and
the six faults at the toy size, the rehearsal cell through `run.py`,
and where the cell is listed."""

import json

import pytest

import brumby_control
import brumby_datagen
import brumby_opcount
import brumby_reference
import harness
import run as bench_run
import seq_readers
from manifest import Manifest

CONFIGS = harness.BENCH_DIR / "configs"
CELL = "brumby14b-hist-c32"
CONFIG = "brumby-14b-pp5-8l"


@pytest.fixture(scope="module")
def arch():
    return brumby_reference.arch(
        harness.load_json(CONFIGS / f"{CONFIG}.json"))


def test_parameters_and_flops_a_token_are_the_sizings_reckoning(arch):
    oc = brumby_opcount
    # W_q and W_o 26,214,400 each, W_k and W_v 5,242,880, the SwiGLU
    # 267,386,880, the gate, the norms and the q/k gains about 51k
    assert oc.layer_params(arch) == 330_352_904
    assert oc.stack_params(arch) == 8 * 330_352_904 + 1_555_824_640 + 5120
    assert oc.stack_params(arch) * 2 / 1e9 == pytest.approx(8.40, abs=0.005)
    assert oc.phi_dims(arch) == 8256
    assert oc.token_matmul_flops(arch) / 1e6 == pytest.approx(660.68,
                                                              abs=0.01)
    assert oc.read_flops(arch) == 40 * (2 * 8256 * 128 + 8256)
    assert oc.update_flops(arch) == 8 * (2 * 8256 * 128 + 8256)
    # a linear mixer's state path is at most 13% of a token's FLOPs
    state = oc.read_flops(arch) + oc.update_flops(arch)
    assert state / (state + oc.token_matmul_flops(arch)) == pytest.approx(
        0.133, abs=0.002)


@pytest.mark.parametrize("chunk,share", [(128, 0.84), (256, 0.72),
                                         (512, 0.55)])
def test_the_traffics_tokens_that_read_a_carried_state(arch, chunk, share):
    """The reckoning from the traffic's own rank-to-length rule: 84% of
    the users' tokens at a chunk of 128."""
    import brumby_datagen as dg
    lengths = dg.history_lengths(4096, median=256, sigma=1.0, lo=16,
                                 hi=2048)
    _, reads, _, tokens = brumby_opcount.retention_counts(
        dict(arch, chunk=chunk), lengths)
    assert reads / tokens == pytest.approx(share, abs=0.01)


def test_the_retention_work_counts_pairs_reads_and_updates(arch):
    a = dict(arch, chunk=4, layers=1, H=2, hkv=1, Dh=2)
    # 6 events at offsets 0..3 of a chunk of 4: pieces 4+2, 3+3, 2+4,
    # 1+4+1; the reads are the events past the first piece, the updates
    # the events before the last piece
    pairs, reads, updates, tokens = brumby_opcount.retention_counts(a, [6])
    assert tokens == 6
    assert pairs == (13 + 12 + 13 + 12) / 4
    assert reads == (2 + 3 + 4 + 5) / 4
    assert updates == (4 + 3 + 2 + 5) / 4
    flops, bytes_ = brumby_opcount.retention_work(a, [6])
    assert flops == (pairs * 2 * 2 * 2 * 2 + reads * 2 * (2 * 3 * 2 + 3)
                     + updates * (2 * 3 * 2 + 3))
    assert bytes_ == 6 * (2 * (2 * 2 + 2 * 1) * 2 + 4)
    assert brumby_opcount.attention_work(a, [6]) == (0.0, 0.0)


def test_retention_roofline_reads_the_drivers_work_and_nothing_without_it():
    class Trace:
        ops = {"power_retention.3": 0.02, "power_retention.9": 0.02,
               "fusion.1": 1.0}

        def module_seconds(self, match):
            return [0.1, 0.1] if match == "^jit_seq_stack" else []

    spec = harness.load_json(harness.BENCH_DIR / "layer_metrics"
                             / "seq_retention_roofline.json")
    assert spec["reader"] == "seq_readers.kernel_roofline"
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    facts = {"trace": Trace(), "peaks": peaks,
             "seq": {"calls": 4, "retention": (4 * 197e12 * 1e-3, 1.0)}}
    # 2 of 4 calls traced: 2 ms of required work over 40 ms of kernel
    assert seq_readers.kernel_roofline(facts, **spec["args"]) \
        == pytest.approx(5.0)
    assert facts["bounds"]["retention"] == "flops"
    del facts["seq"]["retention"]      # the parent's driver counts none
    assert seq_readers.kernel_roofline(facts, **spec["args"]) is None
    assert seq_readers.kernel_roofline({"trace": None}, **spec["args"]) \
        is None


def test_weights_are_bfloat16_values_and_the_same_for_both_sides():
    import jax
    import jax.numpy as jnp
    doc = harness.load_json(CONFIGS / "tiny-brumby.json")
    big = 3_000_000_019                    # more than 32 signed bits hold
    rp = brumby_datagen.reference_params(doc, big)
    pp = brumby_datagen.program_params(doc, big)
    for ref_w, prog_w, std in (
            (rp["l1"]["wq"], pp["l1"]["retention"]["wq"], 1 / 8),
            (rp["l1"]["w_down"], pp["l1"]["ffn"]["w_down"], 1 / 128 ** 0.5),
            (rp["l0"]["w_g"], pp["l0"]["retention"]["w_gate"], 1 / 16),
            (rp["head"], pp["head"], 1 / 8)):
        assert ref_w.dtype == jnp.float32 and prog_w.dtype == jnp.bfloat16
        assert (ref_w.astype(jnp.bfloat16).astype(jnp.float32)
                == ref_w).all()
        assert (prog_w.astype(jnp.float32) == ref_w).all()
        assert float(jnp.std(ref_w)) == pytest.approx(std, rel=0.35)
    assert not bool((rp["head"] == rp["embed"]).all())      # untied
    b = pp["l0"]["retention"]["b_gate"]
    assert b.dtype == jnp.float32 and (b == rp["l0"]["b_g"]).all()
    half_life = -jnp.log(2.0) / jax.nn.log_sigmoid(b)
    lo, hi = doc["assumed"]["gate_half_life"]
    assert bool(((0.999 * lo <= half_life)
                 & (half_life <= 1.001 * hi)).all())
    assert harness.load_json(CONFIGS / f"{CONFIG}.json")["assumed"][
        "gate_half_life"] == [64, 4096]
    assert (rp["l0"]["norm_q"] == 1).all() and (rp["l0"]["norm2"] == 1).all()
    other = brumby_datagen.reference_params(doc, big + 1)
    assert not bool((other["l3"]["wk"] == rp["l3"]["wk"]).all())
    assert all(bool(jnp.isfinite(x.astype(jnp.float32)).all())
               for x in jax.tree_util.tree_leaves(pp))


def test_control_and_faults_read_as_not_correct_at_the_toy_size():
    cell = harness.load_json(harness.BENCH_DIR / "workloads"
                             / "rehearse-brumby-serve.json")
    cfg_file = CONFIGS / "tiny-brumby.json"
    # as the other sequence cells' tests: at hidden 64 bfloat16's
    # rounding alone passes the cell's limits on the widest numbers, so
    # the toy size is held to the rehearsal's limits
    limits = cell["correct"]["limits"]
    got = brumby_control.readings(cell, harness.load_json(cfg_file),
                                  str(cfg_file), 13, 10)
    faults = {f"fault_{name}" for name in brumby_reference.FAULTS}
    assert faults == {"fault_gate_ignored", "fault_drop_normaliser",
                      "fault_no_cut", "fault_no_carry", "fault_drop_sqrt2",
                      "fault_degree1"}
    assert set(got) == {"program", "stated_bf16", "control_fp8"} | faults
    assert brumby_control.failures(got, limits) == [], got
    worst = max(got[name]["score_err_median"]
                for name in brumby_control.MUST_PASS)
    assert worst < limits["score_err_median"] / 1.5
    # with limits this loose every control and fault passes, and that
    # is an exit code
    loose = {name: 100.0 for name in limits}
    assert sorted(brumby_control.failures(got, loose)) \
        == sorted(faults | {"control_fp8"})


def test_rehearsal_cell_runs_whole_and_prints_the_new_metrics():
    manifest = Manifest.load(queued=True)
    out = bench_run.run_cell(manifest, "rehearse-brumby-serve",
                             3_000_000_019, 6.0, True, rehearse_cpu=True)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 20
    got = set(out["metrics"])
    assert {"seq_retention_carry_chunk_pct", "seq_call_tokens_mean",
            "seq_pad_pct", "seq_history_ms_mean",
            "seq_history_in_order_pct", "serve_batch_rows_mean",
            "batch_cycle_ms_mean", "batch_inflight_mean"} <= got \
        <= {x["name"] for x in manifest.per_layer(CELL)}
    assert 0 < out["metrics"]["seq_retention_carry_chunk_pct"]["value"] \
        <= 100
    # the kernels' shares are device times: never on a CPU
    assert not [n for n in got if "roofline" in n or "mfu" in n
                or "device" in n or "share" in n]
    assert set(out["compared"]) == {"rank_gap", "score_err",
                                    "score_err_median", "banned_served",
                                    "short_replies"}
    json.dumps(out)


def test_the_cell_is_listed_on_its_metrics_and_nowhere_else():
    m = Manifest.load()
    assert {x["name"] for x in m.end_to_end(CELL)} == {
        "serve_qps", "serve_p50_ms", "setup_s"}
    mine = {x["name"] for x in m.per_layer(CELL)}
    assert mine == {
        "serve_batch_rows_mean", "serve_server_ms_p50", "serve_tail_ms_p95",
        "serve_worker_wait_ms_mean", "serve_lane_wait_ms_mean",
        "batch_cycle_ms_mean", "batch_host_ms_mean", "batch_inflight_mean",
        "batch_host_cpu_ms_mean", "batch_host_offcpu_ms_mean",
        "gc_pause_pct.serve", "obs_tick_pct.serve", "topk_device_ms",
        "topk_merge_tile_pct", "device_idle_pct.serve", "seq_serve_mfu_pct",
        "seq_stack_device_ms", "seq_call_tokens_mean", "seq_pad_pct",
        "seq_history_ms_mean", "seq_history_in_order_pct",
        "seq_retention_roofline", "seq_retention_kernel_share_pct",
        "seq_retention_carry_chunk_pct"}
    new = {x["name"]: x for x in m.doc["per_layer"]
           if x["name"].startswith("seq_retention_")}
    assert len(new) == 3 and all(
        x["workloads"] == [CELL] and x["moves"] == "serve_qps"
        for x in new.values())
    assert (new["seq_retention_roofline"]["layer"],
            new["seq_retention_kernel_share_pct"]["layer"],
            new["seq_retention_carry_chunk_pct"]["layer"]) == (
                "kernels", "retention mixer", "retention mixer")
    cell = m.cells[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "closed-c32-hist", 1)
    assert len(cell["why"]) <= 200
    cfg = harness.load_json(CONFIGS / f"{CONFIG}.json")
    assert set(m.configs[CONFIG]["reduced"]) == set(cfg["reduced"]) == {
        "num_hidden_layers", "n_users"}
    assert m.configs[CONFIG]["source"] == cfg["source"]
    # the traffic is lfm2-hist-c32's parameter for parameter but for
    # the catalog, which is the vocabulary held
    mine = harness.load_json(harness.BENCH_DIR / "workloads"
                             / f"{CELL}.json")
    lfm2 = harness.load_json(harness.BENCH_DIR / "workloads"
                             / "lfm2-hist-c32.json")
    assert dict(mine["traffic"], n_items=65536) == lfm2["traffic"]
    assert mine["traffic"]["n_items"] == cfg["vocab_size"] == 151936
    assert (mine["warm_bursts"], mine["trace"]) == (lfm2["warm_bursts"],
                                                    lfm2["trace"])
    assert mine["driver"] == "brumby_http_closed"


def test_every_published_number_of_the_catalog_row_is_in_the_file():
    """The catalog row's `config`, key for key, but for the one that
    `reduced` lists; every width as published."""
    cfg = harness.load_json(CONFIGS / f"{CONFIG}.json")
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 5120, "intermediate_size": 17408,
        "max_position_embeddings": 32768, "max_window_layers": 40,
        "model_type": "brumby", "num_attention_heads": 40,
        "num_key_value_heads": 8, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    assert {k: cfg[k] for k in published} == published
    assert cfg["num_hidden_layers"] == 8 == len(cfg["layer_ids"])
    assert cfg["published"]["num_hidden_layers"] == 40
    assumed = cfg["assumed"]
    for key in ("degree", "normaliser", "gate", "qk_norm", "rotary",
                "scale", "epsilon", "chunk", "seeded"):
        assert isinstance(assumed[key], str) and assumed[key], key
    assert (assumed["retention_chunk"], assumed["retention_eps"]) == (
        128, 1e-6)
    assert "stage 0" in cfg["deployment"]
