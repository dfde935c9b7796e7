#!/usr/bin/env python3
"""Chip smoke: the quickstart path, once, on the accelerator.

    python chip_smoke.py

drives the Recommendation template at MovieLens-25M's shape (162,541
users x 59,047 items, rank 64, 10 iterations) through the entry points a
user calls, each a separate CLI process exactly as a user starts them:

    app new -> eventserver (left running) -> import -> train
            -> deploy --batch-window-ms 5 -> POST /queries.json (x48,
               concurrent, some with a blackList) -> /metrics,
               /status.json, `status` -> undeploy
            -> deploy again on the OTHER top-k kernel, same queries,
               same answers -> undeploy

and fails unless every step passed AND the program's own outputs say it
ran where it should: `train` and `deploy` report platform tpu; the event
server never initialised a JAX backend; the solver converged; the serve
plans were warmed (one compile per bucket), no query fell to the host
path, no query compiled; the fused kernel and the XLA chain return the
same items; the compile cache holds entries.

This file never imports jax: a chip belongs to one process, and the
processes it starts need it. Ratings are generated from a seed and
loaded with `pio-tpu import` (sqlite ingests ~20k events/s, so the full
25M do not fit a smoke; the count is printed).

The last line of stdout is the result, and only a full-width run on an
accelerator prints it:

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}

Exit codes: 0 passed on the chip; 10 a reduced or CPU rehearsal passed
(never the pass line); anything else, a step failed and the reason is
the last line of stderr.

Flags that keep the run a full one: `--train-mesh data=4`,
`--serve-mesh items=4` (the four-chip host). Flags that make it a
rehearsal: `--rehearse-cpu` (JAX_PLATFORMS=cpu, small shape), any size
override, and `--inject warmup-fail|chip-held`, which break one thing on
purpose and must make the script fail.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import math
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request
from pathlib import Path

import numpy as np

CHECKOUT = Path(__file__).resolve().parent
FULL = {"users": 162_541, "items": 59_047, "ratings": 3_000_000,
        "rank": 64, "iterations": 10}
REHEARSAL = {"users": 3_000, "items": 1_200, "ratings": 60_000,
             "rank": 64, "iterations": 3}
SERVE_BUCKETS = [1, 2, 4, 8, 16, 32, 64]     # pow2 ladder to batch_max
N_QUERIES = 48
DEADLINE_S = 1150.0      # the contract allows 1200, compilation included
RESIDUAL_GATE = 1e-2     # ops/als.py `_check_residual`
EVENT_TICKS = 2          # event server up for more than this many ticks
TSDB_TICK_S = 5.0        # obs/tsdb.py DEFAULT_INTERVAL_S

BROKEN_ENGINE = '''\
"""The recommendation engine with a warm-up that raises (chip_smoke
--inject warmup-fail): the deploy must fail, not serve unwarmed."""
from predictionio_tpu.core import Engine, FirstServing, IdentityPreparator
from predictionio_tpu.models.recommendation import (
    ALSAlgorithm, RecommendationDataSource,
)


class BrokenWarmup(ALSAlgorithm):
    def warm_serving(self, model, buckets, mesh=None):
        raise RuntimeError("injected warm-up failure (chip_smoke)")


def engine() -> Engine:
    return Engine(data_source=RecommendationDataSource,
                  preparator=IdentityPreparator,
                  algorithms={"als": BrokenWarmup, "": BrokenWarmup},
                  serving=FirstServing)
'''


class StepFailed(Exception):
    pass


class Smoke:
    def __init__(self, args):
        self.args = args
        self.t0 = time.monotonic()
        self.procs = []                 # every process we started
        # inside the checkout (ignored by git), removed at the end
        (CHECKOUT / ".chip_smoke").mkdir(exist_ok=True)
        self.work = Path(tempfile.mkdtemp(prefix="run_",
                                          dir=CHECKOUT / ".chip_smoke"))
        self.env = dict(os.environ)
        self.env["PYTHONPATH"] = os.pathsep.join(
            [str(CHECKOUT), str(self.work)]
            + [p for p in [os.environ.get("PYTHONPATH", "")] if p])
        # serve-state files (dispatch EWMAs, batch-size histogram) stay
        # inside the work dir instead of ~/.pio_store
        self.env["PIO_DISPATCH_STATE"] = str(
            self.work / "serving" / "dispatch_policy.json")
        if args.rehearse_cpu:
            self.env["JAX_PLATFORMS"] = "cpu"

    # -- plumbing -----------------------------------------------------------
    def say(self, msg: str) -> None:
        print(f"[{time.monotonic() - self.t0:7.1f}s] {msg}", flush=True)

    def left(self) -> float:
        left = DEADLINE_S - (time.monotonic() - self.t0)
        if left <= 0:
            raise StepFailed(f"out of time ({DEADLINE_S:.0f}s deadline)")
        return left

    def cli(self, *argv: str, env=None, timeout=None):
        """Run one `pio-tpu` command to its end; returns (stdout JSON or
        None, stderr text). A non-zero exit fails the step."""
        cmd = [sys.executable, "-m", "predictionio_tpu.cli.main", *argv]
        try:
            out = subprocess.run(
                cmd, cwd=self.work, env=env or self.env, text=True,
                capture_output=True, timeout=min(timeout or 1e9,
                                                 self.left()))
        except subprocess.TimeoutExpired:
            raise StepFailed(f"`{' '.join(argv)}` did not finish in time")
        if out.returncode != 0:
            tail = "\n".join(out.stderr.strip().splitlines()[-6:])
            raise StepFailed(
                f"`{' '.join(argv)}` exited {out.returncode}:\n{tail}")
        try:
            parsed = json.loads(out.stdout)
        except ValueError:
            parsed = None
        return parsed, out.stderr

    def spawn(self, name: str, argv, ready: str, env=None,
              timeout: float = 600.0) -> subprocess.Popen:
        """Start a server process and wait for its `ready` stdout line.
        The server dying first fails the step with its stderr tail."""
        log = open(self.work / f"{name}.log", "w+")
        proc = subprocess.Popen(
            argv, cwd=self.work, env=env or self.env, stdout=log,
            stderr=subprocess.STDOUT, text=True, start_new_session=True)
        self.procs.append(proc)
        deadline = time.monotonic() + min(timeout, self.left())
        while time.monotonic() < deadline:
            text = (self.work / f"{name}.log").read_text()
            if ready in text:
                return proc
            if proc.poll() is not None:
                tail = "\n".join(text.strip().splitlines()[-8:])
                raise StepFailed(
                    f"{name} exited {proc.returncode} before it was "
                    f"ready:\n{tail}")
            time.sleep(0.2)
        raise StepFailed(f"{name} was not ready in time")

    def dump_logs(self) -> None:
        """The tail of every server log, to stderr (a failed run's work
        dir is removed, and the reason is usually in there)."""
        for log in sorted(self.work.glob("*.log")):
            tail = log.read_text().strip().splitlines()[-25:]
            print(f"---- {log.name} (last {len(tail)} lines)\n"
                  + "\n".join(tail), file=sys.stderr, flush=True)

    def stop_all(self) -> None:
        for proc in self.procs:
            if proc.poll() is None:
                try:
                    os.killpg(proc.pid, signal.SIGTERM)
                except OSError:
                    pass
        t_end = time.monotonic() + 15
        for proc in self.procs:
            try:
                proc.wait(timeout=max(0.1, t_end - time.monotonic()))
            except subprocess.TimeoutExpired:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except OSError:
                    pass
                proc.wait(timeout=10)

    def http(self, method: str, url: str, body=None, timeout=60.0):
        data = None if body is None else json.dumps(body).encode()
        req = urllib.request.Request(
            url, data=data, method=method,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read().decode()

    def metrics(self, port: int) -> dict:
        """Prometheus text -> {series: value}."""
        _, text = self.http("GET", f"http://127.0.0.1:{port}/metrics")
        out = {}
        for line in text.splitlines():
            if line and not line.startswith("#"):
                series, _, value = line.rpartition(" ")
                try:
                    out[series] = float(value)
                except ValueError:
                    pass
        return out

    @staticmethod
    def free_port() -> int:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    def check(self, ok: bool, what: str) -> None:
        if not ok:
            raise StepFailed(f"check failed, expected: {what}")
        self.say(f"  ok: {what}")

    # -- the steps ----------------------------------------------------------
    def probe_device(self) -> dict:
        """What JAX finds, asked through `pio-tpu status`: it probes the
        device from a child that exits again, so neither this process
        nor `status` ever holds the chip."""
        st, _ = self.cli("status", timeout=180)
        info = st["device"]
        if "device_kind" not in info:
            raise StepFailed(f"no device: {info['platform']}")
        self.say(f"device: platform={info['platform']} "
                 f"device_kind={info['device_kind']} "
                 f"count={info['device_count']}; native codec "
                 f"{st['native']}")
        if info["platform"] == "cpu" and not self.args.rehearse_cpu:
            raise StepFailed(
                "JAX found no accelerator (platform=cpu); a CPU "
                "rehearsal has to be asked for with --rehearse-cpu")
        return info

    def write_ratings(self, path: Path, size: dict) -> int:
        """Seeded low-rank ratings as import-format JSON lines. Every
        user and every item appears, so the factor matrices have the
        full width; half-star values, as MovieLens has."""
        rng = np.random.default_rng(20260926)
        n_u, n_i, n = size["users"], size["items"], size["ratings"]
        n = max(n, n_u, n_i)
        cover = np.arange(max(n_u, n_i))
        users = np.concatenate([cover % n_u,
                                rng.integers(0, n_u, n - len(cover))])
        # popular items draw more ratings (square-law skew)
        skew = (rng.random(n - len(cover)) ** 2 * n_i).astype(np.int64)
        items = np.concatenate([cover % n_i, skew])
        uf = rng.standard_normal((n_u, 8)).astype(np.float32)
        vf = rng.standard_normal((n_i, 8)).astype(np.float32)
        raw = 3.0 + 0.6 * np.einsum("nk,nk->n", uf[users], vf[items]) \
            + 0.3 * rng.standard_normal(n)
        stars = np.clip(np.round(raw * 2) / 2, 0.5, 5.0)
        with open(path, "w") as f:
            for lo in range(0, n, 100_000):
                hi = min(lo + 100_000, n)
                f.write("".join(
                    '{"event":"rate","entityType":"user","entityId":"u%d",'
                    '"targetEntityType":"item","targetEntityId":"i%d",'
                    '"properties":{"rating":%.1f},'
                    '"eventTime":"2020-01-%02dT%02d:%02d:%02d.000Z"}\n'
                    % (u, i, r, 1 + (k // 86400) % 28, (k // 3600) % 24,
                       (k // 60) % 60, k % 60)
                    for k, (u, i, r) in enumerate(
                        zip(users[lo:hi].tolist(), items[lo:hi].tolist(),
                            stars[lo:hi].tolist()), start=lo)))
        return n

    def start_event_server(self, port: int) -> subprocess.Popen:
        argv = ["eventserver", "--ip", "127.0.0.1", "--port", str(port)]
        if self.args.inject == "chip-held":
            # a non-compute server that touches JAX first: it now holds
            # the chip and the check below has to catch it
            code = ("import sys, jax; jax.devices(); "
                    "from predictionio_tpu.cli.main import main; "
                    f"sys.exit(main({argv!r}))")
            cmd = [sys.executable, "-c", code]
        else:
            cmd = [sys.executable, "-m", "predictionio_tpu.cli.main", *argv]
        return self.spawn("eventserver", cmd, "Event server started")

    def event_server_off_chip(self, port: int, when: str) -> None:
        m = self.metrics(port)
        self.check("pio_jax_backend_initialized" in m,
                   f"event server reports pio_jax_backend_initialized "
                   f"({when})")
        self.check(m["pio_jax_backend_initialized"] == 0.0,
                   f"event server has not initialised a JAX backend "
                   f"({when})")

    def deploy(self, name: str, port: int, extra_env=None,
               extra_args=()) -> subprocess.Popen:
        env = dict(self.env, **(extra_env or {}))
        argv = [sys.executable, "-m", "predictionio_tpu.cli.main", "deploy",
                "--ip", "127.0.0.1", "--port", str(port),
                "--batch-window-ms", "5", *extra_args]
        if self.args.serve_mesh:
            argv += ["--mesh", self.args.serve_mesh]
        return self.spawn(name, argv, "Engine server started", env=env)

    def undeploy(self, proc: subprocess.Popen, port: int) -> None:
        self.cli("undeploy", "--ip", "127.0.0.1", "--port", str(port),
                 timeout=60)
        try:
            rc = proc.wait(timeout=min(60, self.left()))
        except subprocess.TimeoutExpired:
            raise StepFailed("the server did not exit after undeploy")
        self.check(rc == 0, f"the server exited 0 after undeploy (rc={rc})")

    def ask(self, port: int, queries) -> list:
        """POST every query concurrently; returns the parsed answers in
        query order. Any non-200 fails the step."""
        url = f"http://127.0.0.1:{port}/queries.json"

        def one(q):
            status, body = self.http("POST", url, q)
            if status != 200:
                raise StepFailed(f"query {q} answered {status}: {body}")
            return json.loads(body)

        with concurrent.futures.ThreadPoolExecutor(16) as pool:
            return list(pool.map(one, queries))

    def check_answers(self, queries, answers) -> None:
        for q, a in zip(queries, answers):
            scores = a.get("itemScores")
            if not scores or len(scores) != q["num"]:
                raise StepFailed(f"query {q} -> {len(scores or [])} items, "
                                 f"wanted {q['num']}")
            values = [s["score"] for s in scores]
            if not all(math.isfinite(v) for v in values):
                raise StepFailed(f"query {q} -> non-finite score")
            if values != sorted(values, reverse=True):
                raise StepFailed(f"query {q} -> scores not descending")
            banned = set(q.get("blackList") or ())
            hit = banned & {s["item"] for s in scores}
            if hit:
                raise StepFailed(f"query {q} returned black-listed {hit}")

    # -- the run ------------------------------------------------------------
    def run(self) -> dict:
        a = self.args
        size = dict(REHEARSAL if a.rehearse_cpu else FULL)
        for key in size:
            if getattr(a, key) is not None:
                size[key] = getattr(a, key)
        full = (size == FULL and not a.rehearse_cpu and not a.inject)
        self.say(f"checkout {CHECKOUT}; work dir {self.work}; "
                 f"{'FULL' if full else 'REHEARSAL'} run {size}")

        device = self.probe_device()

        app, _ = self.cli("app", "new", "smoke", timeout=120)
        self.say(f"app new: id={app['id']}")

        es_port = self.free_port()
        self.start_event_server(es_port)
        es_started = time.monotonic()
        for n in range(5):
            status, body = self.http(
                "POST", f"http://127.0.0.1:{es_port}/events.json"
                        f"?accessKey={app['accessKey']}",
                {"event": "rate", "entityType": "user",
                 "entityId": f"u{n}", "targetEntityType": "item",
                 "targetEntityId": f"i{n}",
                 "properties": {"rating": 4.0}})
            self.check(status == 201 and "eventId" in body,
                       f"event {n} POSTed through the event server")
        self.event_server_off_chip(es_port, "after its first events")

        t = time.monotonic()
        n_ratings = self.write_ratings(self.work / "ratings.json", size)
        self.say(f"generated {n_ratings} ratings "
                 f"({size['users']} users x {size['items']} items) "
                 f"in {time.monotonic() - t:.1f}s")
        t = time.monotonic()
        imported, _ = self.cli("import", "--appid", str(app["id"]),
                               "--input", "ratings.json")
        took = time.monotonic() - t
        self.check(imported["imported"] == n_ratings,
                   f"imported {imported['imported']} ratings in "
                   f"{took:.1f}s ({n_ratings / took:,.0f} events/s)")

        wait = EVENT_TICKS * TSDB_TICK_S + 1 \
            - (time.monotonic() - es_started)
        if wait > 0:
            time.sleep(wait)
        self.event_server_off_chip(
            es_port, f"up {time.monotonic() - es_started:.0f}s, more than "
                     f"{EVENT_TICKS} scrape ticks, before train")

        (self.work / "engine.json").write_text(json.dumps({
            "id": "default", "engineFactory": "recommendation",
            "datasource": {"params": {"app_name": "smoke"}},
            "algorithms": [{"name": "als", "params": {
                "rank": size["rank"],
                "num_iterations": size["iterations"],
                "lambda_": 0.05, "seed": 1}}]}))
        train_argv = ["train"]
        if a.train_mesh:
            train_argv += ["--mesh", a.train_mesh]
        t = time.monotonic()
        trained, report = self.cli(*train_argv)
        self.say(f"train took {time.monotonic() - t:.1f}s: "
                 f"{json.dumps(trained)}")
        print(report.rstrip(), flush=True)
        self.check(trained["status"] == "COMPLETED", "train COMPLETED")
        want = "cpu" if a.rehearse_cpu else device["platform"]
        self.check(trained["device"]["platform"] == want
                   and (a.rehearse_cpu or want != "cpu"),
                   f"train ran on platform={trained['device']['platform']} "
                   f"device_kind={trained['device']['device_kind']} "
                   f"count={trained['device']['device_count']} "
                   f"mesh={trained['mesh']}")
        phases = trained["phaseTimings"]
        residual = phases.get("solver_residual")
        self.check(residual is not None and math.isfinite(residual)
                   and residual < RESIDUAL_GATE,
                   f"solver_residual {residual} under {RESIDUAL_GATE} "
                   "(finite factors: Engine.train ran the model's "
                   "sanity_check)")
        self.check(trained["jaxCompiles"] > 0,
                   f"train phase timings {phases}; jaxCompiles="
                   f"{trained['jaxCompiles']}; compile cache "
                   f"{trained['compileCache']}")

        # -- serve: the default kernel --------------------------------------
        port = self.free_port()
        if a.inject == "warmup-fail":
            (self.work / "broken_engine.py").write_text(BROKEN_ENGINE)
            self.deploy("deploy", port, extra_args=(
                "--engine-factory", "broken_engine.engine"))
            raise StepFailed("a deploy whose warm-up raises came up "
                             "anyway")
        server = self.deploy("deploy", port)
        ready = time.monotonic()
        first = {"user": "u0", "num": 10}
        top = self.ask(port, [first])[0]
        self.check_answers([first], [top])
        c0 = self.metrics(port)["pio_jax_backend_compiles_total"]
        seen = [s["item"] for s in top["itemScores"]]
        queries = []
        for n in range(N_QUERIES):
            q = {"user": f"u{(n * 7919) % size['users']}", "num": 10}
            if n % 3 == 0:
                q["blackList"] = seen[: 1 + n % 5]
            if n % 3 == 0 and n % 2 == 0:
                q["user"] = "u0"    # its own top items, banned
            queries.append(q)
        answers = self.ask(port, queries)
        self.check_answers(queries, answers)
        self.say(f"  ok: {len(queries)} concurrent queries answered, "
                 f"{sum('blackList' in q for q in queries)} with a "
                 "blackList, none returned a black-listed item")
        m = self.metrics(port)
        _, body = self.http("GET", f"http://127.0.0.1:{port}/status.json")
        status = json.loads(body)
        self.say(f"/status.json: {json.dumps(status)}")
        self.check(status["device"]["platform"] == want,
                   f"deploy ran on platform={status['device']['platform']} "
                   f"device_kind={status['device']['device_kind']} "
                   f"count={status['device']['device_count']}")
        plan = status["servePlans"][0]
        self.check([int(b) for b in plan["buckets"]] == SERVE_BUCKETS,
                   f"{plan['plan']} warmed buckets {plan['buckets']}")
        warm = m.get("pio_serve_warmup_compiles_total", 0)
        self.check(warm == len(plan["buckets"]),
                   f"pio_serve_warmup_compiles_total {warm:.0f} == bucket "
                   f"count {len(plan['buckets'])}")
        paths = {p: m.get('pio_topk_dispatch_total{path="%s"}' % p, 0.0)
                 for p in ("host", "device", "fused", "sharded")}
        self.check(paths["host"] == 0 and sum(paths.values()) > 0,
                   f"top-k dispatches {paths}: none on the host")
        if a.serve_mesh:
            shards = int(a.serve_mesh.split("=")[1])
            self.check(m.get("pio_serve_shards") == shards
                       and paths["sharded"] > 0,
                       f"pio_serve_shards {m.get('pio_serve_shards')} "
                       f"== {shards} and sharded dispatches > 0")
            per_shard = {k: v for k, v in m.items()
                         if k.startswith("pio_serve_shard_bytes")}
            self.check(len(per_shard) == shards
                       and len(set(per_shard.values())) == 1,
                       f"factor bytes per shard {per_shard}: no device "
                       "holds the whole catalog")
        c1 = m["pio_jax_backend_compiles_total"]
        self.check(c1 == c0, "pio_jax_backend_compiles_total flat across "
                             f"the queries ({c0:.0f} -> {c1:.0f})")
        cache_m = {r: m.get('pio_jax_compile_cache_total{result="%s"}' % r,
                            0.0) for r in ("hit", "miss")}
        self.say(f"  compile cache of the deploy: {cache_m}")
        # the server samples device memory on its tsdb tick: give it one
        time.sleep(max(0.0, TSDB_TICK_S + 1 - (time.monotonic() - ready)))
        hbm = {k: v for k, v in self.metrics(port).items()
               if k.startswith("pio_device_memory_bytes")}
        self.say(f"  device memory as the server sampled it: {hbm}")

        # `status` while the server holds the chip: it must answer
        # (not hang) and must not take the chip itself
        t = time.monotonic()
        st, _ = self.cli("status", timeout=120)
        self.say(f"`pio-tpu status` with the chip held answered in "
                 f"{time.monotonic() - t:.1f}s: device={st['device']} "
                 f"native={st['native']}")
        self.check(st["storage"] == "ok", "status: storage ok")
        self.undeploy(server, port)

        # -- serve again on the other kernel: same answers -------------------
        kernels = set(plan["buckets"].values())
        other = "off" if kernels == {"fused"} else "on"
        port2 = self.free_port()
        server2 = self.deploy("deploy_other", port2,
                              extra_env={"PIO_SERVE_FUSED": other})
        _, body = self.http("GET", f"http://127.0.0.1:{port2}/status.json")
        plan2 = json.loads(body)["servePlans"][0]
        kernels2 = set(plan2["buckets"].values())
        self.check(kernels2 and kernels2 != kernels,
                   f"second deploy (PIO_SERVE_FUSED={other}) serves "
                   f"{plan2['buckets']}")
        answers2 = self.ask(port2, queries)
        self.check_answers(queries, answers2)
        worst = 0.0
        for q, x, y in zip(queries, answers, answers2):
            ids_x = [s["item"] for s in x["itemScores"]]
            ids_y = [s["item"] for s in y["itemScores"]]
            if ids_x != ids_y:
                raise StepFailed(f"{kernels} and {kernels2} disagree on "
                                 f"{q}: {ids_x} vs {ids_y}")
            for sx, sy in zip(x["itemScores"], y["itemScores"]):
                worst = max(worst, abs(sx["score"] - sy["score"])
                            / max(1.0, abs(sx["score"])))
        self.check(worst < 1e-4,
                   f"{sorted(kernels)} and {sorted(kernels2)} return the "
                   f"same items for all {len(queries)} queries (worst "
                   f"relative score difference {worst:.2e})")
        self.undeploy(server2, port2)

        self.event_server_off_chip(es_port, "at the end")
        cache = Path(trained["compileCache"]["dir"])
        entries = sum(1 for p in cache.rglob("*") if p.is_file()) \
            if cache.is_dir() else 0
        self.check(entries > 0,
                   f"compile cache {cache} holds {entries} files; train "
                   f"reported {trained['compileCache']}")
        return {"full": full, "device": device, "ratings": n_ratings}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--train-mesh", help="e.g. data=4 (four-chip host)")
    ap.add_argument("--serve-mesh", help="e.g. items=4 (four-chip host)")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="small shape on JAX_PLATFORMS=cpu; never prints "
                         "the pass line")
    ap.add_argument("--inject", choices=["warmup-fail", "chip-held"],
                    help="break one thing on purpose: the run must fail")
    for key in FULL:
        ap.add_argument(f"--{key}", type=int,
                        help="size override (makes the run a rehearsal)")
    ap.add_argument("--keep", action="store_true",
                    help="keep the work dir")
    args = ap.parse_args()
    smoke = Smoke(args)
    try:
        result = smoke.run()
    except StepFailed as e:
        smoke.say("FAILED")
        smoke.dump_logs()
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    except Exception as e:   # the boundary: report, stop children, fail
        import traceback
        traceback.print_exc()
        smoke.dump_logs()
        print(f"chip_smoke FAILED: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        return 1
    finally:
        smoke.stop_all()
        if not args.keep:
            shutil.rmtree(smoke.work, ignore_errors=True)
    device = result["device"]
    smoke.say(f"all steps passed: platform={device['platform']} "
              f"device_kind={device['device_kind']} "
              f"count={device['device_count']} ratings={result['ratings']}")
    if not result["full"]:
        print(f"REHEARSAL passed (platform={device['platform']}); this is "
              "not the chip result", flush=True)
        return 10
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["device_kind"],
        "count": device["device_count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
