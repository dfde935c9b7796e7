"""The static-analysis gate, run in the suite the way the reference CI
runs scalastyle + Apache RAT on every build (`tests/unit.sh:31-35`)."""

from pathlib import Path

from predictionio_tpu.tools import lint


def test_lint_gate_clean():
    violations = lint.run(Path(__file__).resolve().parents[1])
    assert not violations, "\n".join(violations)


def test_lint_catches_violations(tmp_path):
    bad = tmp_path / "predictionio_tpu" / "bad.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(
        "import os\n"                       # unused import, no docstring
        "def f(x=[]):\n"                    # mutable default
        "    try:\n        pass\n"
        "    except:\n        pass\n"       # bare except
    )
    out = lint.run(tmp_path)
    kinds = "\n".join(out)
    assert "missing module docstring" in kinds
    assert "unused import" in kinds
    assert "mutable default" in kinds
    assert "bare 'except:'" in kinds


def test_string_annotations_count_as_usage(tmp_path):
    f = tmp_path / "predictionio_tpu" / "ok.py"
    f.parent.mkdir(parents=True)
    f.write_text(
        '"""doc"""\n'
        "from typing import Mapping\n"
        "def g(x: \"Mapping[str, int]\") -> None:\n"
        "    return None\n"
    )
    assert not lint.run(tmp_path)


def test_instrumentation_gate_catches_print_and_time(tmp_path):
    bad = tmp_path / "predictionio_tpu" / "serving" / "bad.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(
        '"""doc"""\n'
        "import time\n"
        "def f():\n"
        "    print('served')\n"
        "    t0 = time.time()\n"
        "    return t0\n"
    )
    kinds = "\n".join(lint.run(tmp_path))
    assert "bare print()" in kinds
    assert "naked time.time()" in kinds


def test_instrumentation_gate_scoped_to_obs_layers(tmp_path):
    # cli/ and tools/ are operator-facing: print is their output channel
    ok = tmp_path / "predictionio_tpu" / "cli" / "fine.py"
    ok.parent.mkdir(parents=True)
    ok.write_text(
        '"""doc"""\n'
        "import time\n"
        "def f():\n"
        "    print(time.time())\n"
    )
    assert not lint.run(tmp_path)


def test_instrumentation_gate_line_escape(tmp_path):
    f = tmp_path / "predictionio_tpu" / "data" / "ttl.py"
    f.parent.mkdir(parents=True)
    f.write_text(
        '"""doc"""\n'
        "import time\n"
        "def fresh(mtime, ttl):\n"
        "    return time.time() - mtime < ttl  # lint: ok\n"
    )
    assert not lint.run(tmp_path)


def test_bounded_wait_gate_catches_unbounded_wait_and_sleep(tmp_path):
    bad = tmp_path / "predictionio_tpu" / "serving" / "hangs.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(
        '"""doc"""\n'
        "import time\n"
        "def f(done):\n"
        "    done.wait()\n"
        "    time.sleep(1)\n"
    )
    kinds = "\n".join(lint.run(tmp_path))
    assert "unbounded .wait()" in kinds
    assert "bare time.sleep()" in kinds


def test_bounded_wait_gate_allows_timeouts_and_escapes(tmp_path):
    ok = tmp_path / "predictionio_tpu" / "data" / "waits.py"
    ok.parent.mkdir(parents=True)
    ok.write_text(
        '"""doc"""\n'
        "import time\n"
        "def f(done):\n"
        "    done.wait(5.0)\n"
        "    time.sleep(0.01)  # lint: ok\n"
    )
    assert not lint.run(tmp_path)


def test_bounded_wait_gate_scoped_to_resilient_layers(tmp_path):
    # core/ and cli/ are not request/storage paths
    ok = tmp_path / "predictionio_tpu" / "core" / "fine.py"
    ok.parent.mkdir(parents=True)
    ok.write_text(
        '"""doc"""\n'
        "def f(done):\n"
        "    done.wait()\n"
    )
    assert not lint.run(tmp_path)


def test_storage_write_gate_catches_direct_writes(tmp_path):
    bad = tmp_path / "predictionio_tpu" / "data" / "storage" / "torn.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(
        '"""doc"""\n'
        "def save(path, blob, note):\n"
        "    path.write_bytes(blob)\n"
        "    path.write_text(note)\n"
    )
    kinds = "\n".join(lint.run(tmp_path))
    assert "direct .write_bytes()" in kinds
    assert "direct .write_text()" in kinds
    assert "atomic_write_bytes" in kinds


def test_storage_write_gate_allows_tmp_and_escape(tmp_path):
    ok = tmp_path / "predictionio_tpu" / "data" / "storage" / "ok.py"
    ok.parent.mkdir(parents=True)
    ok.write_text(
        '"""doc"""\n'
        "def save(path, blob):\n"
        "    path.with_suffix('.tmp').write_bytes(blob)\n"
        "    path.write_bytes(blob)  # lint: ok\n"
    )
    assert not lint.run(tmp_path)


def test_storage_write_gate_scoped_to_storage_drivers(tmp_path):
    # data/ outside storage/ is not under the atomic-write mandate
    ok = tmp_path / "predictionio_tpu" / "data" / "fine.py"
    ok.parent.mkdir(parents=True)
    ok.write_text(
        '"""doc"""\n'
        "def save(path, blob):\n"
        "    path.write_bytes(blob)\n"
    )
    assert not lint.run(tmp_path)


def test_device_transfer_gate_catches_implicit_syncs(tmp_path):
    bad = tmp_path / "predictionio_tpu" / "serving" / "sync.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(
        '"""doc"""\n'
        "import numpy as np\n"
        "def f(dev_scores, row):\n"
        "    host = np.asarray(dev_scores)\n"
        "    copy = np.array(dev_scores)\n"
        "    s = float(dev_scores)\n"
        "    return host, copy, s\n"
    )
    kinds = "\n".join(lint.run(tmp_path))
    assert "np.asarray() on a device hot path" in kinds
    assert "np.array() on a device hot path" in kinds
    assert "float() coercion on a device hot path" in kinds
    assert "jax.device_get" in kinds


def test_device_transfer_gate_allows_host_scalars_and_escape(tmp_path):
    ok = tmp_path / "predictionio_tpu" / "serving" / "fine.py"
    ok.parent.mkdir(parents=True)
    ok.write_text(
        '"""doc"""\n'
        "import numpy as np\n"
        "def f(pending, raw, cfg):\n"
        "    depth = float(len(pending))\n"       # len() is host
        "    ms = float(cfg.window_ms)\n"         # attribute constant
        "    arr = np.asarray(raw)  # lint: ok\n"
        "    return depth, ms, arr\n"
    )
    assert not lint.run(tmp_path)


def test_device_transfer_gate_scoped_to_hot_paths(tmp_path):
    # models/ assemble host-side results; np coercions are their job
    ok = tmp_path / "predictionio_tpu" / "models" / "fine.py"
    ok.parent.mkdir(parents=True)
    ok.write_text(
        '"""doc"""\n'
        "import numpy as np\n"
        "def f(scores):\n"
        "    return float(np.asarray(scores)[0])\n"
    )
    assert not lint.run(tmp_path)


def test_urlopen_gate_catches_unbounded_dials(tmp_path):
    bad = tmp_path / "predictionio_tpu" / "serving" / "dials.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(
        '"""doc"""\n'
        "from urllib.request import urlopen\n"
        "import urllib.request\n"
        "def f(url):\n"
        "    a = urlopen(url)\n"
        "    b = urllib.request.urlopen(url)\n"
        "    return a, b\n"
    )
    kinds = "\n".join(lint.run(tmp_path))
    assert kinds.count("urlopen() without timeout=") == 2


def test_urlopen_gate_allows_timeouts_and_escape(tmp_path):
    ok = tmp_path / "predictionio_tpu" / "data" / "dials.py"
    ok.parent.mkdir(parents=True)
    ok.write_text(
        '"""doc"""\n'
        "from urllib.request import urlopen\n"
        "def f(url, budget):\n"
        "    a = urlopen(url, timeout=budget)\n"
        "    b = urlopen(url)  # lint: ok\n"
        "    return a, b\n"
    )
    assert not lint.run(tmp_path)


def test_urlopen_gate_scoped_to_request_paths(tmp_path):
    # tools/ scripts may block on a slow peer; only serving/data must bound
    ok = tmp_path / "predictionio_tpu" / "tools" / "fetch.py"
    ok.parent.mkdir(parents=True)
    ok.write_text(
        '"""doc"""\n'
        "from urllib.request import urlopen\n"
        "def f(url):\n"
        "    return urlopen(url)\n"
    )
    assert not lint.run(tmp_path)


def test_training_read_gate_catches_find_events_in_read_training(tmp_path):
    bad = tmp_path / "predictionio_tpu" / "models" / "tmpl.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(
        '"""doc"""\n'
        "from predictionio_tpu.data import store\n"
        "def read_training(ctx):\n"
        "    return list(store.find_events(ctx.registry, 'app'))\n"
    )
    kinds = "\n".join(lint.run(tmp_path))
    assert "store.find_events() in read_training" in kinds
    assert "rating_columns" in kinds


def test_training_read_gate_line_escape_and_other_functions(tmp_path):
    ok = tmp_path / "predictionio_tpu" / "models" / "fine.py"
    ok.parent.mkdir(parents=True)
    ok.write_text(
        '"""doc"""\n'
        "from predictionio_tpu.data import store\n"
        "def read_training(ctx):\n"
        "    return list(store.find_events(ctx.registry, 'a'))  # lint: ok\n"
        "def history(ctx):\n"   # serve-time reads are fine
        "    return list(store.find_events(ctx.registry, 'a'))\n"
    )
    assert not lint.run(tmp_path)


def test_training_read_gate_scoped_to_models(tmp_path):
    # outside models/ a read_training helper may stream Events
    ok = tmp_path / "predictionio_tpu" / "core" / "fine.py"
    ok.parent.mkdir(parents=True)
    ok.write_text(
        '"""doc"""\n'
        "from predictionio_tpu.data import store\n"
        "def read_training(ctx):\n"
        "    return list(store.find_events(ctx.registry, 'a'))\n"
    )
    assert not lint.run(tmp_path)


def test_streaming_accumulation_gate_catches_module_state(tmp_path):
    bad = tmp_path / "predictionio_tpu" / "streaming" / "leaky.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(
        '"""doc"""\n'
        "_HISTORY = []\n"
        "_SEEN: list = []\n"
        "def tick(delta):\n"
        "    _HISTORY.append(delta)\n"
        "    _SEEN.extend(delta)\n"
    )
    kinds = "\n".join(lint.run(tmp_path))
    assert ".append() into module-level '_HISTORY'" in kinds
    assert ".extend() into module-level '_SEEN'" in kinds
    assert "across refresh ticks" in kinds


def test_streaming_accumulation_gate_allows_local_and_escape(tmp_path):
    ok = tmp_path / "predictionio_tpu" / "streaming" / "fine.py"
    ok.parent.mkdir(parents=True)
    ok.write_text(
        '"""doc"""\n'
        "_RING = []\n"
        "def tick(deltas):\n"
        "    batch = []\n"            # tick-local: dies with the tick
        "    for d in deltas:\n"
        "        batch.append(d)\n"
        "    _RING.append(batch)  # lint: ok\n"
        "    del _RING[:-8]\n"
        "    return batch\n"
    )
    assert not lint.run(tmp_path)


def test_streaming_accumulation_gate_scoped_to_streaming(tmp_path):
    # outside streaming/ module-level accumulation is not per-tick
    ok = tmp_path / "predictionio_tpu" / "core" / "registry.py"
    ok.parent.mkdir(parents=True)
    ok.write_text(
        '"""doc"""\n'
        "_ENGINES = []\n"
        "def register(e):\n"
        "    _ENGINES.append(e)\n"
    )
    assert not lint.run(tmp_path)


def test_hot_route_gate_catches_json_and_dicts(tmp_path):
    bad = tmp_path / "predictionio_tpu" / "serving" / "server.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(
        '"""doc"""\n'
        "import json\n"
        "def _fast_queries(raw):\n"
        "    obj = json.loads(raw.body)\n"
        "    headers = {k: v for k, v in raw.header_items()}\n"
        "    return json.dumps({'itemScores': obj}).encode()\n"
    )
    kinds = "\n".join(lint.run(tmp_path))
    assert "json.loads() in hot-route '_fast_queries'" in kinds
    assert "json.dumps() in hot-route '_fast_queries'" in kinds
    assert "dict comprehension in hot-route" in kinds
    assert "dict literal in hot-route" in kinds


def test_hot_route_gate_allows_escape_and_cold_functions(tmp_path):
    ok = tmp_path / "predictionio_tpu" / "utils" / "wire.py"
    ok.parent.mkdir(parents=True)
    ok.write_text(
        '"""doc"""\n'
        "import json\n"
        "def _service(conn):\n"
        "    d = dict(a=1)\n"              # constructor call: explicit
        "    return json.dumps(d)  # lint: ok (fallback)\n"
        "def legacy_route(body):\n"        # not a hot-route function
        "    return json.loads(body), {'x': 1}\n"
    )
    assert not lint.run(tmp_path)


def test_hot_route_gate_scoped_to_wire_files(tmp_path):
    # the same names elsewhere are not the wire hot path
    ok = tmp_path / "predictionio_tpu" / "serving" / "other.py"
    ok.parent.mkdir(parents=True)
    ok.write_text(
        '"""doc"""\n'
        "import json\n"
        "def _fast_thing(body):\n"
        "    return json.loads(body)\n"
    )
    assert not lint.run(tmp_path)


def test_hot_route_gate_catches_fstrings_and_trace_materialization(tmp_path):
    bad = tmp_path / "predictionio_tpu" / "serving" / "server.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(
        '"""doc"""\n'
        "from predictionio_tpu.obs import trace\n"
        "def _fast_queries(raw, rid):\n"
        "    tag = f'req-{rid}'\n"
        "    trace.traces_json_body(raw.query_get)\n"
        "    return tag\n"
    )
    kinds = "\n".join(lint.run(tmp_path))
    assert "f-string in hot-route '_fast_queries'" in kinds
    assert "trace.traces_json_body() in hot-route '_fast_queries'" in kinds
    assert "stamp-only API" in kinds


def test_hot_route_gate_allows_stamp_api_and_escapes(tmp_path):
    ok = tmp_path / "predictionio_tpu" / "utils" / "wire.py"
    ok.parent.mkdir(parents=True)
    ok.write_text(
        '"""doc"""\n'
        "from predictionio_tpu.obs import trace\n"
        "def _fast_queries(raw, e):\n"
        "    trace.stamp(raw, trace.S_EXEC)\n"     # stamp-only API: fine
        "    trace.annotate(raw, dispatch='host')\n"
        "    msg = f'{type(e).__name__}: {e}'  # lint: ok (error path)\n"
        "    return msg\n"
        "def render(rid):\n"                       # not a hot-route function
        "    return f'req-{rid}'\n"
    )
    assert not lint.run(tmp_path)


def test_hot_route_gate_allows_the_stage_helper_only(tmp_path):
    # the stage helper stamps into the cycle's record: allowed on the
    # hot route; opening, ending or keeping a record is not
    ok = tmp_path / "predictionio_tpu" / "utils" / "wire.py"
    ok.parent.mkdir(parents=True)
    ok.write_text(
        '"""doc"""\n'
        "from predictionio_tpu.obs import trace\n"
        "def _fast_queries(raw):\n"
        "    with trace.stage('pack'):\n"
        "        pass\n"
        "    h = trace.stage_open('launch')\n"
        "    trace.stage_close(h)\n"
        "    trace.note_dispatch('fused', 64)\n"
    )
    assert not lint.run(tmp_path)
    bad = tmp_path / "predictionio_tpu" / "serving" / "server.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(
        '"""doc"""\n'
        "from predictionio_tpu.obs import trace\n"
        "def _fast_queries(raw, hist):\n"
        "    bt = trace.batch_begin(hist)\n"
        "    trace.batch_end(bt)\n"
        "    trace.get_recorder().record_batch(bt)\n"
    )
    kinds = "\n".join(lint.run(tmp_path))
    assert "trace.batch_begin() in hot-route '_fast_queries'" in kinds
    assert "trace.batch_end() in hot-route '_fast_queries'" in kinds
    assert "trace.get_recorder() in hot-route '_fast_queries'" in kinds
    assert "stage_open" in kinds          # the message lists what is allowed


def test_hot_route_trace_gate_scoped_to_wire_files(tmp_path):
    # trace materialization outside the wire files is the normal API
    ok = tmp_path / "predictionio_tpu" / "tools" / "page.py"
    ok.parent.mkdir(parents=True)
    ok.write_text(
        '"""doc"""\n'
        "from predictionio_tpu.obs import trace\n"
        "def _fast_render(q):\n"
        "    return trace.traces_json_body(q), f'n={len(q)}'\n"
    )
    assert not lint.run(tmp_path)


def test_hot_route_gate_covers_egress_functions(tmp_path):
    # PR 13 extends the hot set to the gathered-egress/batch-flush path
    bad = tmp_path / "predictionio_tpu" / "utils" / "wire.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(
        '"""doc"""\n'
        "import json\n"
        "def _flush_locked(conn, wait):\n"
        "    meta = {'fd': conn.fd}\n"
        "def _flush_pass(self):\n"
        "    tag = f'reactor-{self.index}'\n"
        "def _mark_sent(self, item):\n"
        "    return json.dumps(item)\n"
    )
    kinds = "\n".join(lint.run(tmp_path))
    assert "dict literal in hot-route '_flush_locked'" in kinds
    assert "f-string in hot-route '_flush_pass'" in kinds
    assert "json.dumps() in hot-route '_mark_sent'" in kinds


def test_hot_route_gate_covers_binary_codec(tmp_path):
    bad = tmp_path / "predictionio_tpu" / "utils" / "wire.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(
        '"""doc"""\n'
        "import json\n"
        "def decode_bin_query(body):\n"
        "    return json.loads(body)\n"       # the point is NOT to
        "def encode_bin_query(user, num):\n"
        "    return {'user': user, 'num': num}\n"
    )
    kinds = "\n".join(lint.run(tmp_path))
    assert "json.loads() in hot-route 'decode_bin_query'" in kinds
    assert "dict literal in hot-route 'encode_bin_query'" in kinds


def test_tenant_growth_gate_catches_unbounded_maps(tmp_path):
    bad = tmp_path / "predictionio_tpu" / "tenancy" / "leaky.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(
        '"""doc"""\n'
        "class Ctl:\n"
        "    def __init__(self):\n"
        "        self._tenants = {}\n"
        "        self.lanes = {}\n"
        "    def admit(self, app, v):\n"
        "        self._tenants[app] = v\n"
        "        self.lanes.setdefault(app, []).append(v)\n"
    )
    kinds = "\n".join(lint.run(tmp_path))
    assert "subscript-assign into tenant-keyed '_tenants'" in kinds
    assert ".setdefault() into tenant-keyed 'lanes'" in kinds
    assert "per-principal state" in kinds


def test_tenant_growth_gate_allows_escape_and_other_names(tmp_path):
    ok = tmp_path / "predictionio_tpu" / "serving" / "fine.py"
    ok.parent.mkdir(parents=True)
    ok.write_text(
        '"""doc"""\n'
        "class Batcher:\n"
        "    def __init__(self):\n"
        "        self._tenants = {}\n"
        "        self._size_counts = {}\n"     # not tenant-keyed
        "    def put(self, app, v, n):\n"
        "        self._tenants[app] = v  # lint: ok (evicted at cap)\n"
        "        self._size_counts[n] = 1\n"
    )
    assert not lint.run(tmp_path)


def test_tenant_growth_gate_scoped_to_tenancy_and_serving(tmp_path):
    # outside tenancy//serving/ a tenant-named dict is not admission
    # state (e.g. a train-time per-app aggregation, bounded by the run)
    ok = tmp_path / "predictionio_tpu" / "tools" / "report.py"
    ok.parent.mkdir(parents=True)
    ok.write_text(
        '"""doc"""\n'
        "def summarize(rows):\n"
        "    tenants = {}\n"
        "    for r in rows:\n"
        "        tenants[r.app] = r\n"
        "    return tenants\n"
    )
    assert not lint.run(tmp_path)


def test_thread_name_gate_catches_anonymous_threads(tmp_path):
    bad = tmp_path / "predictionio_tpu" / "serving" / "bg.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(
        '"""doc"""\n'
        "import threading\n"
        "def f(work):\n"
        "    t = threading.Thread(target=work, daemon=True)\n"
        "    t.start()\n"
        "    return t\n"
    )
    kinds = "\n".join(lint.run(tmp_path))
    assert "threading.Thread without name=" in kinds


def test_thread_name_gate_allows_named_and_escape(tmp_path):
    ok = tmp_path / "predictionio_tpu" / "serving" / "bg.py"
    ok.parent.mkdir(parents=True)
    ok.write_text(
        '"""doc"""\n'
        "import threading\n"
        "from threading import Thread\n"
        "def f(work):\n"
        "    a = threading.Thread(target=work, daemon=True,\n"
        "                         name='pio-bg-worker')\n"
        "    b = Thread(target=work)  # lint: ok — test scaffold\n"
        "    return a, b\n"
    )
    assert not lint.run(tmp_path)


def test_thread_name_gate_scoped_to_package(tmp_path):
    # tests/ and bench.py spawn throwaway threads whose names carry no
    # role information — the gate only guards the package itself
    ok = tmp_path / "tests" / "test_x.py"
    ok.parent.mkdir(parents=True)
    ok.write_text(
        '"""doc"""\n'
        "import threading\n"
        "def f(work):\n"
        "    return threading.Thread(target=work)\n"
    )
    assert not lint.run(tmp_path)


def test_pager_thread_gate_catches_serve_path_paging(tmp_path):
    bad = tmp_path / "predictionio_tpu" / "serving" / "hotloop.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(
        '"""doc"""\n'
        "def serve(plan, vecs, banned):\n"
        "    plan.fold_accesses()\n"
        "    plan.rebalance()\n"
        "    return plan(vecs, banned)\n"
    )
    kinds = "\n".join(lint.run(tmp_path))
    assert ".fold_accesses() belongs on the async page thread" in kinds
    assert ".rebalance() belongs on the async page thread" in kinds


def test_pager_thread_gate_allows_pager_and_escape(tmp_path):
    # serving/paging.py IS the page thread; elsewhere the line escape
    # marks a deliberate pager-driven call site
    pager = tmp_path / "predictionio_tpu" / "serving" / "paging.py"
    pager.parent.mkdir(parents=True)
    pager.write_text(
        '"""doc"""\n'
        "def tick(plans):\n"
        "    for plan in plans:\n"
        "        plan.fold_accesses()\n"
        "        plan.rebalance()\n"
    )
    ok = tmp_path / "predictionio_tpu" / "serving" / "admin.py"
    ok.write_text(
        '"""doc"""\n'
        "def force_page(plan):\n"
        "    plan.fold_accesses()  # lint: ok — operator-forced page\n"
        "    return plan.rebalance()  # lint: ok — operator-forced page\n"
    )
    assert not lint.run(tmp_path)


def test_pager_thread_gate_scoped_to_package(tmp_path):
    # tests and benches drive paging deterministically by design
    ok = tmp_path / "tests" / "test_x.py"
    ok.parent.mkdir(parents=True)
    ok.write_text(
        '"""doc"""\n'
        "def drive(plan):\n"
        "    plan.fold_accesses()\n"
        "    plan.rebalance()\n"
    )
    assert not lint.run(tmp_path)


def test_ingest_materialization_gate_catches_whole_store_reads(tmp_path):
    bad = tmp_path / "predictionio_tpu" / "ingest" / "service.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(
        '"""doc"""\n'
        "def run(store):\n"
        "    evs = list(store.find(1))\n"
        "    cols = store.scan_columns(1)\n"
        "    return evs, cols\n"
    )
    kinds = "\n".join(lint.run(tmp_path))
    assert "walks Event objects" in kinds
    assert "block-budget" in kinds


def test_ingest_materialization_gate_allows_budgeted_scan(tmp_path):
    ok = tmp_path / "predictionio_tpu" / "ingest" / "service.py"
    ok.parent.mkdir(parents=True)
    ok.write_text(
        '"""doc"""\n'
        "def run(store):\n"
        "    return store.scan_columns(1)  # block-budget: BLOCK_ROWS\n"
    )
    assert not lint.run(tmp_path)


def test_ingest_materialization_gate_scoped_to_service(tmp_path):
    # the client and pipeline legitimately call scan_columns plain
    ok = tmp_path / "predictionio_tpu" / "ingest" / "client.py"
    ok.parent.mkdir(parents=True)
    ok.write_text(
        '"""doc"""\n'
        "def run(store):\n"
        "    return store.scan_columns(1)\n"
    )
    assert not lint.run(tmp_path)
