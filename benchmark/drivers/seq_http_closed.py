"""Driver `seq_http_closed`: a real `PredictionServer` in this process
over a sequence-recommender instance whose backbone is the cell's
configuration, and the traffic generator as a child process that never
opens a JAX backend.

Set-up: the program's new modules are imported FIRST (a program without
them fails here, at once, before any child exists); the stack's weights
are drawn on the device from the seed (`seq_datagen.program_params`);
every user's history is written to the MEM event store as `view`
events; the server loads the instance through `prepare_deploy` ->
`warm_deploy` (token buckets and top-k buckets compiled ahead of time);
the child is given the run's seed and warms up. Window and trace as
`http_closed`. After the close and with the server's arrays deleted: a
seeded sample of the replies the window returned, plus the one with the
longest history and the one with the longest ban list, against
`seq_reference` over each user's regenerated history, layer by layer,
one layer's float32 weights at a time; `reference.compare_replies` /
`verdict` decide, scores being logits: the widest `score_err` and
`rank_gap` over the sample, as the ALS cells, and the sample's median
`score_err` (`compare_logits`, below).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from datetime import datetime, timedelta, timezone
from typing import Any, Dict, List

import numpy as np

import harness
import reference
import seq_datagen
import seq_opcount
import seq_reference
from drivers.http_closed import _read_tagged, _sample, _store_instance

APP = "benchapp"


def user_lengths(cfg: Dict[str, Any], traffic: Dict[str, Any]) -> np.ndarray:
    h = traffic["history"]
    return seq_datagen.history_lengths(
        int(cfg["n_users"]), median=float(h["median"]),
        sigma=float(h["sigma"]), lo=int(h["min"]), hi=int(h["max"]))


def write_histories(registry, lengths: np.ndarray, items: np.ndarray) -> int:
    """Every user's history into the event store: user rank r is
    `u<r>`, item rank i is `i<i>`, one `view` a second."""
    from predictionio_tpu.data.event import DataMap, Event
    from predictionio_tpu.data.storage import App
    app_id = registry.get_meta_data_apps().insert(App(0, APP))
    events = registry.get_events()
    events.init(app_id)
    t0 = datetime(2026, 1, 1, tzinfo=timezone.utc)
    stamps = [t0 + timedelta(seconds=j) for j in range(int(lengths.max()))]
    none = DataMap({})
    at = 0
    for r, n in enumerate(lengths):
        user = f"u{r}"
        events.insert_batch(
            [Event(event="view", entity_type="user", entity_id=user,
                   target_entity_type="item",
                   target_entity_id=f"i{int(items[at + j])}",
                   properties=none, event_time=stamps[j],
                   event_id=f"{user}e{j}")
             for j in range(int(n))], app_id)
        at += int(n)
    return app_id


def run(rc: harness.RunContext) -> Dict[str, Any]:
    # the program's part first: a checkout without it stops here
    from predictionio_tpu.ops import backbone, moe          # noqa: F401
    from predictionio_tpu.ops.seqrec import PackedEncoder   # noqa: F401
    from predictionio_tpu.core import EngineParams, workflow
    from predictionio_tpu.ingest.bimap import BiMap
    from predictionio_tpu.models import seqrec as sr
    from predictionio_tpu.obs import compile_count, install_compile_probe
    from predictionio_tpu.ops.seqrec import SeqRecModel
    from predictionio_tpu.serving import PredictionServer, ServerConfig

    cfg, a, traffic = rc.config, rc.config["assumed"], rc.cell["traffic"]
    arch = seq_reference.arch(cfg)
    n_users, n_items, k = int(cfg["n_users"]), arch["V"], int(a["k"])
    if (traffic["loop"], traffic["n_users"], traffic["n_items"]) != (
            "closed", n_users, n_items):
        raise ValueError("the traffic's loop or populations are not the "
                         "configuration's")
    config_file = str(harness.BENCH_DIR / "configs"
                      / f"{rc.cell['config']}.json")
    bcfg = backbone.load_config(config_file)

    t = time.perf_counter()
    import jax
    params = seq_datagen.program_params(cfg, rc.seed)
    jax.block_until_ready(params)
    rc.note("weights_s", round(time.perf_counter() - t, 3))
    rc.note("parameters", backbone.n_params(bcfg))
    t = time.perf_counter()
    lengths = user_lengths(cfg, traffic)
    items = seq_datagen.histories(lengths, n_items,
                                  float(traffic["item_zipf_s"]), rc.seed)
    ends = np.cumsum(lengths)
    registry = harness.mem_registry()
    write_histories(registry, lengths, items)
    rc.note("events", int(lengths.sum()))
    rc.note("events_write_s", round(time.perf_counter() - t, 3))

    t = time.perf_counter()
    model = sr.SeqRecServingModel(
        SeqRecModel(params=params, n_items=n_items,
                    backbone=backbone.config_dict(bcfg)),
        BiMap({f"u{n}": n for n in range(n_users)}),
        BiMap({f"i{n}": n for n in range(n_items)}))
    del params
    engine = sr.engine()
    eparams = EngineParams(
        data_source_params=("", sr.DataSourceParams(app_name=APP)),
        algorithm_params_list=(("seqrec", sr.SeqRecParams(
            app_name=APP, event_names=("view",),
            backbone=config_file)),))
    _store_instance(registry, eparams)
    # as `http_closed`: the deploy path is taken whole but for the
    # pickled blob, which for 6.9 GB of device arrays would be a round
    # trip through the host that no deployment of this size makes
    install_compile_probe()
    original = workflow.deserialize_models
    workflow.deserialize_models = lambda *a_, **k_: [model]
    try:
        server = PredictionServer(
            ServerConfig(ip="127.0.0.1", port=0,
                         batch_window_ms=int(a["batch_window_ms"]),
                         batch_max=int(a["batch_max"])),
            registry=registry, engine=engine)
    finally:
        workflow.deserialize_models = original
    del model
    port = server.start()
    rc.note("server_load_s", round(time.perf_counter() - t, 3))
    encoder, plan = server._dep.algos[0]._plans(server._dep.models[0])
    rc.note("serve_plan", {"token_buckets": list(encoder.buckets),
                           "rows": encoder.rows,
                           "class": type(plan).__name__,
                           "bucket_kernels": plan.bucket_kernels()})
    del encoder, plan

    env = {k_: v for k_, v in os.environ.items()
           if not k_.startswith(("JAX_", "XLA_", "TPU_"))}
    child = subprocess.Popen(
        [sys.executable, str(harness.BENCH_DIR / "loadgen.py")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env)
    try:
        child.stdin.write(json.dumps({
            "traffic": traffic, "seed": rc.seed, "port": port,
            "seconds": rc.seconds,
            "warm_bursts": rc.cell["warm_bursts"]}) + "\n")
        child.stdin.flush()
        warm = _read_tagged(child, "WARM")
        rc.note("warmup", warm)
        if warm["failed"]:
            raise RuntimeError(f"warm-up requests failed: {warm}")

        before = harness.registry_snapshot(server.metrics)
        compiles0 = compile_count()
        t0 = time.perf_counter()
        setup_s = t0 - rc.t_start
        child.stdin.write("GO\n")
        child.stdin.flush()
        traced: Dict[str, Any] = {"reduced": None}
        if rc.trace:
            time.sleep(float(rc.cell["trace"]["after_s"]))
            with harness.profiler_window() as traced:
                time.sleep(min(float(rc.cell["trace"]["seconds"]),
                               max(rc.seconds - 1.5, 0.5)))
        done = _read_tagged(child, "DONE")
        child.wait(timeout=30)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    after = harness.registry_snapshot(server.metrics)
    rc.note("compiles_in_window", compile_count() - compiles0)
    peak = harness.device_memory_peak()
    rc.note("memory_peak_bytes", peak)
    rc.note("generator", {x: done[x] for x in (
        "attempted", "failed", "ok_in_window", "seconds", "cpu_share",
        "connections", "late_ms_p99", "p50_ms", "p95_ms")})
    hist = harness.snapshot_delta(before, after)
    server.shutdown()
    del server, registry, engine
    harness.free_device()
    rc.note("peak_rss_bytes", harness.peak_rss_bytes())

    # what the completed queries required, for the shares of the peaks
    good = [r for r in done["records"]
            if r["status"] == 200 and r["ids"] is not None]
    scale = done["ok_in_window"] / max(len(good), 1)
    got = [int(lengths[r["user"]]) for r in good]
    calls = (hist.get("pio_seq_call_tokens") or {}).get("count", 0)
    # the window's calls' own (token, held expert) pairs, not uniform
    # routing's: seeded routers send this chip 0.9 to 1.4 times those
    pairs = (hist.get("pio_moe_expert_pairs") or {}).get("sum")
    attn = seq_opcount.attention_work(arch, got)
    seq_facts = {"tokens": scale * sum(got), "calls": calls,
                 "attn": tuple(scale * x for x in attn)}
    if pairs is not None:
        seq_facts["moe"] = seq_opcount.moe_work(arch, pairs, calls)
        seq_facts["serve_flops"] = (
            scale * seq_opcount.serve_flops(arch, got, 0.0)
            + seq_facts["moe"][0])
        rc.note("expert_pairs_over_uniform", round(
            pairs / max(seq_opcount.uniform_pairs(
                arch, seq_facts["tokens"]), 1.0), 4))
    rc.note("tokens_per_s", round(seq_facts["tokens"] / done["seconds"], 1))

    t = time.perf_counter()
    sample = _sample(done["records"], int(rc.cell["correct"]["sample"]),
                     rc.seed)
    if good:                       # and the longest history served
        longest = max(good, key=lambda r: lengths[r["user"]])
        if all(r["i"] != longest["i"] for r in sample):
            sample.append(longest)
    numbers = {"rank_gap": float("inf"), "score_err": float("inf"),
               "score_err_median": float("inf"),
               "banned_served": 0.0, "short_replies": 0.0}
    if sample:
        hs = [items[ends[r["user"]] - lengths[r["user"]]:ends[r["user"]]]
              for r in sample]
        logits = seq_reference.forward_layerwise(
            cfg, seq_datagen.layer_stream(cfg, rc.seed), hs)
        numbers = compare_logits(sample, logits, k,
                                 int(traffic["banned_max"]))
    rc.note("reference_s", round(time.perf_counter() - t, 3))
    rc.note("replies_compared", len(sample))
    correct, compared = reference.verdict(numbers,
                                          rc.cell["correct"]["limits"])
    # a reply that never came, or came malformed, is for `correct` too
    correct = correct and done["failed"] == 0 and bool(sample)

    def ms(x: float) -> float:
        return x if np.isfinite(x) else harness.SLOWER_THAN_ANY_LIMIT_MS

    return {
        "correct": correct, "compared": compared,
        "attempted": done["attempted"], "failed": done["failed"],
        "memory_peak_bytes": peak,
        "end_to_end": {"serve_qps": done["ok_in_window"] / done["seconds"],
                       "serve_p50_ms": ms(done["p50_ms"]),
                       "serve_p95_ms": ms(done["p95_ms"]),
                       "setup_s": setup_s},
        "facts": {"hist": hist, "trace": traced["reduced"],
                  "completed": done["ok_in_window"],
                  "window_s": done["seconds"],
                  "client_p95_ms": done["p95_ms"],
                  "gen_late_ms_p99": done["late_ms_p99"],
                  "seq": seq_facts},
    }


def compare_logits(replies: List[Dict], logits: np.ndarray, k: int,
                   banned_max: int) -> Dict[str, float]:
    """`reference.compare_replies` over the reference's logits: its
    candidates are each row's k + banned_max + 6 best, enough for the
    k best allowed whatever the request banned. Beside the sample's
    widest `score_err` and `rank_gap`, `score_err_median` over its
    replies: an expert selection that flips on rounding moves a few
    replies far (the widest reads 0.004 to 0.06 by seed), while a fault
    in the stack (a dropped sink, a shifted share) moves every reply,
    so the median tells the two apart where the widest cannot."""
    n = min(logits.shape[1], k + banned_max + 6)
    top_i = np.argsort(-logits, axis=1, kind="stable")[:, :n]
    top_s = np.take_along_axis(logits, top_i, axis=1)
    served_ref = np.zeros((len(replies), k), np.float64)
    for q, r in enumerate(replies):
        ids = np.asarray(r["ids"][:k], np.int64)
        served_ref[q, :len(ids)] = logits[q, ids]
    each = [reference.compare_replies(
        [{"ids": r["ids"], "scores": r["scores"],
          "banned": r["banned"] or []}],
        top_s[q:q + 1], top_i[q:q + 1], served_ref[q:q + 1], k)
        for q, r in enumerate(replies)]
    out = {name: (max if name in ("score_err", "rank_gap") else sum)(
        e[name] for e in each) for name in each[0]}
    out["score_err_median"] = float(np.median(
        [e["score_err"] for e in each if not e["short_replies"]] or [0.0]))
    return out
