"""In-repo static-analysis gate (the reference CI runs scalastyle and
Apache RAT on every build, `tests/unit.sh:31-35` + `scalastyle-config.xml`;
this is the Python analog, stdlib-only because the image ships no linter).

Checks, per source file:
  - parses (syntax gate)
  - has a module docstring (the RAT header-audit role: every file must
    declare what it is; the repo's convention also cites the reference
    file it re-designs)
  - no tabs in indentation, no trailing whitespace
  - line length <= MAX_LINE
  - no bare ``except:`` (scalastyle's catch-Throwable rule)
  - no mutable default arguments
  - no unused imports (module scope; ``__init__.py`` re-export files
    are exempt, matching their role as a public surface)
  - instrumented layers (serving/, data/, core/) must not use bare
    ``print(`` or naked ``time.time()`` — telemetry goes through
    predictionio_tpu.obs (structured logs, histograms) so it is
    scrapable and request-correlated instead of lost on stdout
  - resilient layers (serving/, data/) must not call ``.wait()`` with
    no timeout (a crashed peer strands the waiter forever — pass a
    bound, see predictionio_tpu.resilience.Deadline) nor ``time.sleep``
    (hand-rolled retry pacing: use resilience.call_with_retry, which is
    jittered, bounded, and deadline-aware)
  - storage drivers (data/storage/) must not ``.write_bytes(`` /
    ``.write_text(`` a durable path directly — a crash mid-write leaves
    a torn file; go through ``data.integrity.atomic_write_bytes`` (tmp +
    fsync + rename). Lines mentioning ``.tmp`` (the staging file of the
    atomic pattern itself) or marked ``# lint: ok`` are allowed
  - resilient layers (serving/, data/) must pass an explicit
    ``timeout=`` to every ``urllib.request.urlopen(`` call — the
    default is "wait forever", and a hung peer (partitioned replica,
    dead router) then strands the calling thread with it; derive the
    bound from the remaining deadline budget where one exists
  - device serve hot paths (ops/topk.py, serving/) must not coerce with
    ``np.asarray``/``np.array`` or bare ``float()``/``int()`` — on a jax
    array each is an implicit device->host transfer that blocks the
    accelerator mid-pipeline; read back once per dispatch with
    ``jax.device_get`` (known-host inputs: ``# lint: ok``)
  - streaming hot loops (streaming/) must not ``.append``/``.extend``
    into module-level state — the refresher ticks forever, so any
    per-tick accumulation into process-lifetime state is an unbounded
    memory leak; keep per-tick state tick-local, or mark a genuinely
    bounded accumulator ``# lint: ok``
  - the serve wire hot route (serving/server.py fast-path functions,
    utils/wire.py framing/service loop) must not call ``json.dumps``/
    ``json.loads`` or build dict literals per request — the 10k-qps
    wire path exists precisely because per-request dict assembly and
    generic JSON (de)serialization dominated the old stack; responses
    are spliced from pre-encoded fragments and headers are scanned in
    place. ``dict(...)`` constructor calls pass (rare, explicit);
    ``# lint: ok`` on the line is the escape hatch for documented
    fallbacks (e.g. the encoder-declined single serialization). The
    same functions must not build f-strings per request, and may call
    the flight recorder only through its stamp-slot API (stamp/mark/
    begin_raw/annotate/stage/...) — materialization belongs in on_sent
    and batch_end
  - tenancy layers (tenancy/, serving/) must not grow tenant-keyed
    containers unboundedly — ``x[...] = ...`` / ``.setdefault(`` on a
    name containing ``tenant``/``lane`` is per-REMOTE-PRINCIPAL state:
    an attacker cycling access keys (or a fleet serving many apps)
    grows it forever. Route the state through a capped structure
    (``tenancy.admission.BoundedTenantMap``) or mark a write whose
    bound is enforced elsewhere ``# lint: ok``

Escape hatch: a line containing ``# lint: ok`` is skipped for line-based
rules; a file listed in EXEMPT is skipped entirely.
"""

from __future__ import annotations

import ast
import sys
from pathlib import Path
from typing import Iterator, List, Tuple

MAX_LINE = 88

# files exempt from all checks (none today; the hook exists so a
# generated file can be excluded without weakening the gate)
EXEMPT: Tuple[str, ...] = ()

_MUTABLE = (ast.List, ast.Dict, ast.Set, ast.ListComp, ast.DictComp,
            ast.SetComp)

# layers whose telemetry must flow through predictionio_tpu.obs
_OBS_DIRS = ("predictionio_tpu/serving/", "predictionio_tpu/data/",
             "predictionio_tpu/core/", "predictionio_tpu/tenancy/")

# storage drivers: every durable write must be crash-atomic
_STORAGE_DIRS = ("predictionio_tpu/data/storage/",)

# layers where unbounded waits and ad-hoc sleep loops are forbidden —
# everything on a request or storage path must finish or fail in
# bounded time (predictionio_tpu.resilience supplies the bounded forms)
_RESILIENT_DIRS = ("predictionio_tpu/serving/", "predictionio_tpu/data/",
                   "predictionio_tpu/tenancy/")

# device hot paths: implicit device->host transfers (np.asarray /
# np.array / float() on a jax array) force a blocking sync per call
_DEVICE_HOT_PATHS = ("predictionio_tpu/ops/topk.py",
                     "predictionio_tpu/ops/topk_sharded.py",
                     "predictionio_tpu/ops/topk_tiered.py",
                     "predictionio_tpu/serving/")

# demand-paged tier: slab promotion (`.rebalance()`) and access folding
# (`.fold_accesses()`) gather + re-upload the hot slab — strictly the
# async page thread's job (serving/paging.PageManager). Called from a
# serve or request path they re-serialize every query behind a device
# upload.
_PAGER_FILES = ("predictionio_tpu/serving/paging.py",)

# template data sources: training reads must use the columnar scan
_MODELS_DIRS = ("predictionio_tpu/models/",)

# streaming hot loops: the refresher ticks for the process lifetime, so
# accumulating into module-level state grows without bound
_STREAMING_DIRS = ("predictionio_tpu/streaming/",)

# multi-tenant admission layers: tenant-keyed state is per-REMOTE-
# PRINCIPAL memory, which an access-key-cycling client grows at will
_TENANCY_DIRS = ("predictionio_tpu/tenancy/", "predictionio_tpu/serving/")

# the serve wire hot route: files and function names on the
# per-request path where generic JSON and dict assembly are banned
_HOT_ROUTE_FILES = ("predictionio_tpu/serving/server.py",
                    "predictionio_tpu/utils/wire.py",
                    "predictionio_tpu/obs/quality.py")
_HOT_ROUTE_FUNCS = ("frame_request", "build_response", "header",
                    "_service", "_pump",
                    # sendmsg egress + cross-wakeup batch flush
                    "_flush_out", "_flush_locked", "_mark_sent",
                    "flush_hint", "_flush_pass",
                    # binary query framing (SDK fast lane)
                    "encode_bin_query", "decode_bin_query",
                    "_decode_bin_slow",
                    # quality accumulators' serve-path entry point
                    "observe_result")

# the flight-recorder calls allowed on the hot route: stamp-slot writes
# and deferred annotation only — anything else (materialization, ring
# access, id generation) allocates or locks per request and belongs in
# on_sent/finish, which run after the response bytes are queued. The
# stage helper (stage / stage_open / stage_close) stamps an interval
# into the batch cycle's record and enters a profiler span; what it
# stamped is observed and kept in batch_end, on the drainer's thread.
_HOT_TRACE_API = ("stamp", "mark", "begin_raw", "annotate",
                  "annotate_pending", "add_span", "on_sent", "new_stamps",
                  "current", "child_header", "ensure_ids",
                  "stage", "stage_open", "stage_close", "note_dispatch")

# container-name fragments the tenant-growth rule keys on
_TENANT_NAME_FRAGMENTS = ("tenant", "lane")

# files where the same rule additionally keys on app-labelled maps:
# the quality accumulators are keyed by the serve-path app label, which
# a key-cycling client mints at will — every map there must be
# LRU-capped (and its writes marked '# lint: ok')
_APP_KEYED_FILES = ("predictionio_tpu/obs/quality.py",)


def _used_names(tree: ast.AST) -> set:
    used = set()

    def add_string_annotation(s: str) -> None:
        try:
            sub = ast.parse(s, mode="eval")
        except SyntaxError:
            return
        for n in ast.walk(sub):
            if isinstance(n, ast.Name):
                used.add(n.id)

    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            used.add(node.id)
        elif isinstance(node, ast.Attribute):
            n = node
            while isinstance(n, ast.Attribute):
                n = n.value
            if isinstance(n, ast.Name):
                used.add(n.id)
        # string (forward-reference) annotations reference names too
        elif isinstance(node, (ast.AnnAssign, ast.arg)) \
                and isinstance(node.annotation, ast.Constant) \
                and isinstance(node.annotation.value, str):
            add_string_annotation(node.annotation.value)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and isinstance(node.returns, ast.Constant) \
                and isinstance(node.returns.value, str):
            add_string_annotation(node.returns.value)
    return used


def _check_imports(tree: ast.Module, rel: str) -> Iterator[str]:
    if rel.endswith("__init__.py"):
        return   # re-export surface
    used = _used_names(tree)
    # names referenced in module docstring-level __all__ count as used
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == "__all__" \
                        and isinstance(node.value, (ast.List, ast.Tuple)):
                    for elt in node.value.elts:
                        if isinstance(elt, ast.Constant):
                            used.add(str(elt.value))
    for node in tree.body:   # module scope only: local imports are often
        # deliberate (lazy jax import pattern used across the repo)
        if isinstance(node, ast.Import):
            for alias in node.names:
                name = (alias.asname or alias.name).split(".")[0]
                if name not in used:
                    yield (f"{rel}:{node.lineno}: unused import "
                           f"'{alias.name}'")
        elif isinstance(node, ast.ImportFrom):
            if node.module == "__future__":
                continue
            for alias in node.names:
                if alias.name == "*":
                    continue
                name = alias.asname or alias.name
                if name not in used:
                    yield (f"{rel}:{node.lineno}: unused import "
                           f"'{alias.name}'")


def _check_defaults(tree: ast.AST, rel: str) -> Iterator[str]:
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for d in list(node.args.defaults) + [
                    d for d in node.args.kw_defaults if d is not None]:
                if isinstance(d, _MUTABLE):
                    yield (f"{rel}:{node.lineno}: mutable default "
                           f"argument in '{node.name}'")


def _check_excepts(tree: ast.AST, rel: str) -> Iterator[str]:
    for node in ast.walk(tree):
        if isinstance(node, ast.ExceptHandler) and node.type is None:
            yield f"{rel}:{node.lineno}: bare 'except:'"


def _check_lines(text: str, rel: str) -> Iterator[str]:
    for n, line in enumerate(text.splitlines(), 1):
        if "# lint: ok" in line:
            continue
        stripped = line.rstrip("\n")
        if stripped != stripped.rstrip():
            yield f"{rel}:{n}: trailing whitespace"
        if "\t" in stripped:
            yield f"{rel}:{n}: tab character"
        if len(stripped) > MAX_LINE:
            yield f"{rel}:{n}: line length {len(stripped)} > {MAX_LINE}"


def _check_instrumentation(tree: ast.AST, text: str,
                           rel: str) -> Iterator[str]:
    """In serving/, data/, core/: no bare print(), no naked time.time().
    ``# lint: ok`` on the line is the escape hatch for legitimate
    wall-clock uses (TTL comparisons, backoff sleeps computing deadlines).
    """
    if not rel.startswith(_OBS_DIRS):
        return
    lines = text.splitlines()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        line = lines[node.lineno - 1] if node.lineno <= len(lines) else ""
        if "# lint: ok" in line:
            continue
        fn = node.func
        if isinstance(fn, ast.Name) and fn.id == "print":
            yield (f"{rel}:{node.lineno}: bare print() in an "
                   "instrumented layer; use predictionio_tpu.obs "
                   "structured logging")
        elif isinstance(fn, ast.Attribute) and fn.attr == "time" \
                and isinstance(fn.value, ast.Name) \
                and fn.value.id == "time":
            yield (f"{rel}:{node.lineno}: naked time.time() timing; "
                   "use a predictionio_tpu.obs histogram timer "
                   "(perf_counter inside) or mark '# lint: ok' for "
                   "legitimate wall-clock use")


def _check_bounded_waits(tree: ast.AST, text: str,
                         rel: str) -> Iterator[str]:
    """In serving/ and data/: forbid no-argument ``.wait()`` (an
    Event/Condition wait with no timeout hangs forever when the peer
    that would set it has died — satellite (a) of the resilience PR was
    exactly this bug) and bare ``time.sleep(...)`` (hand-rolled retry
    pacing; resilience.call_with_retry is the jittered, deadline-aware
    form). ``# lint: ok`` on the line is the escape hatch for the few
    legitimate uses (batch-window pacing, documented backstops)."""
    if not rel.startswith(_RESILIENT_DIRS):
        return
    lines = text.splitlines()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        line = lines[node.lineno - 1] if node.lineno <= len(lines) else ""
        if "# lint: ok" in line:
            continue
        fn = node.func
        if not isinstance(fn, ast.Attribute):
            continue
        if fn.attr == "wait" and not node.args and not node.keywords:
            yield (f"{rel}:{node.lineno}: unbounded .wait() — a dead "
                   "setter strands this thread forever; pass a timeout "
                   "(deadline.remaining() or a documented backstop), "
                   "or mark '# lint: ok'")
        elif fn.attr == "sleep" and isinstance(fn.value, ast.Name) \
                and fn.value.id == "time":
            yield (f"{rel}:{node.lineno}: bare time.sleep() in a "
                   "resilient layer; use resilience.call_with_retry "
                   "for retry pacing, or mark '# lint: ok' for "
                   "legitimate fixed waits")


def _check_thread_names(tree: ast.AST, text: str,
                        rel: str) -> Iterator[str]:
    """In predictionio_tpu/: every ``threading.Thread(...)`` must pass
    ``name=`` — the sampling profiler attributes CPU samples to roles
    by thread-name prefix (obs/profiler.py), so an anonymous
    ``Thread-12`` is a hole in every /profile.json. ``# lint: ok`` on
    the construction line is the escape hatch."""
    if not rel.startswith("predictionio_tpu/"):
        return
    lines = text.splitlines()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        is_thread = (
            (isinstance(fn, ast.Attribute) and fn.attr == "Thread"
             and isinstance(fn.value, ast.Name)
             and fn.value.id == "threading")
            or (isinstance(fn, ast.Name) and fn.id == "Thread"))
        if not is_thread:
            continue
        line = lines[node.lineno - 1] if node.lineno <= len(lines) else ""
        if "# lint: ok" in line:
            continue
        name_kw = next((kw for kw in node.keywords
                        if kw.arg == "name"), None)
        if name_kw is None:
            yield (f"{rel}:{node.lineno}: threading.Thread without "
                   "name= — profiler role attribution needs named "
                   "threads (obs/profiler.py); pass name='pio-...' or "
                   "mark '# lint: ok'")
            continue
        # the name must carry a role prefix: the profiler buckets by
        # prefix, and the watchdog's stall dumps are useless against
        # a thread named 'worker' — lambdas passed as target= have no
        # function name to fall back on, so the prefix is the ONLY
        # role signal
        head = None
        v = name_kw.value
        if isinstance(v, ast.Constant) and isinstance(v.value, str):
            head = v.value
        elif isinstance(v, ast.JoinedStr) and v.values \
                and isinstance(v.values[0], ast.Constant):
            head = str(v.values[0].value)
        if head is not None and not head.startswith(("pio-", "wire-")):
            yield (f"{rel}:{node.lineno}: thread name {head!r} lacks a "
                   "role prefix; use 'pio-<role>...' or 'wire-...' so "
                   "the profiler/watchdog can attribute it, or mark "
                   "'# lint: ok'")


def _check_urlopen_timeout(tree: ast.AST, text: str,
                           rel: str) -> Iterator[str]:
    """In serving/ and data/: every ``urlopen(`` must carry an explicit
    ``timeout=`` kwarg. urllib's default is socket-global (usually
    None = block forever), so a partitioned peer that accepts the TCP
    connection and then goes silent strands the caller — on the fleet
    data path that means a router thread gone for good. The bound
    should come from the remaining deadline budget when the call is on
    a request path (``min(cap, deadline.remaining())``). ``# lint: ok``
    on the line is the escape hatch."""
    if not rel.startswith(_RESILIENT_DIRS):
        return
    lines = text.splitlines()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        name = fn.attr if isinstance(fn, ast.Attribute) else (
            fn.id if isinstance(fn, ast.Name) else "")
        if name != "urlopen":
            continue
        line = lines[node.lineno - 1] if node.lineno <= len(lines) else ""
        if "# lint: ok" in line:
            continue
        if any(kw.arg == "timeout" for kw in node.keywords):
            continue
        yield (f"{rel}:{node.lineno}: urlopen() without timeout= blocks "
               "forever on a silent peer; pass an explicit bound "
               "(deadline-derived on request paths), or mark "
               "'# lint: ok'")


def _check_storage_writes(tree: ast.AST, text: str,
                          rel: str) -> Iterator[str]:
    """In data/storage/: forbid direct ``.write_bytes()``/``.write_text()``
    — a crash between open and close leaves a torn durable file that the
    next reader trips over. The atomic pattern (integrity.atomic_write_
    bytes: unique tmp, fsync, rename, fsync dir) is the sanctioned form.
    A line naming ``.tmp`` (the staging write inside that very pattern,
    or an intentionally-torn fault injection) or marked ``# lint: ok``
    passes."""
    if not rel.startswith(_STORAGE_DIRS):
        return
    lines = text.splitlines()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        if not isinstance(fn, ast.Attribute) \
                or fn.attr not in ("write_bytes", "write_text"):
            continue
        line = lines[node.lineno - 1] if node.lineno <= len(lines) else ""
        if "# lint: ok" in line or ".tmp" in line:
            continue
        yield (f"{rel}:{node.lineno}: direct .{fn.attr}() in a storage "
               "driver tears on crash; use "
               "data.integrity.atomic_write_bytes (or mark '# lint: ok')")


def _check_device_transfers(tree: ast.AST, text: str,
                            rel: str) -> Iterator[str]:
    """On the device serve hot paths (ops/topk.py, serving/): forbid
    ``np.asarray(``/``np.array(`` and ``float(``/``int(`` coercions —
    each one is a potential implicit device->host transfer that blocks
    on the accelerator and re-serializes the pipeline. The sanctioned
    forms are explicit: ``jax.device_get(...)`` for one batched readback
    per dispatch, or ``# lint: ok`` on a line whose input is known
    host-resident. ``float(``/``int(`` on obvious host scalars
    (constants, ``len(...)``, each other) pass without annotation."""
    if not rel.startswith(_DEVICE_HOT_PATHS):
        return
    lines = text.splitlines()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        line = lines[node.lineno - 1] if node.lineno <= len(lines) else ""
        if "# lint: ok" in line:
            continue
        fn = node.func
        if isinstance(fn, ast.Attribute) \
                and fn.attr in ("asarray", "array") \
                and isinstance(fn.value, ast.Name) \
                and fn.value.id in ("np", "numpy"):
            yield (f"{rel}:{node.lineno}: np.{fn.attr}() on a device "
                   "hot path is an implicit device->host transfer; use "
                   "jax.device_get once per dispatch, or mark "
                   "'# lint: ok' for known-host inputs")
        elif isinstance(fn, ast.Name) and fn.id in ("float", "int") \
                and node.args:
            arg = node.args[0]
            # host-scalar coercions are fine: literals, len()/int()/
            # float()/min()/max() results, attribute constants
            if isinstance(arg, ast.Constant):
                continue
            if isinstance(arg, ast.Call) \
                    and isinstance(arg.func, ast.Name) \
                    and arg.func.id in ("len", "int", "float", "min",
                                        "max", "round"):
                continue
            # method-call results (os.environ.get, dict lookups) are
            # host values; device reads go through jax.device_get first
            if isinstance(arg, ast.Call) \
                    and isinstance(arg.func, ast.Attribute):
                continue
            if isinstance(arg, (ast.BinOp, ast.Attribute)):
                continue
            yield (f"{rel}:{node.lineno}: {fn.id}() coercion on a "
                   "device hot path may force a device sync; coerce "
                   "after jax.device_get, or mark '# lint: ok' for "
                   "host values")


def _check_pager_thread(tree: ast.AST, text: str,
                        rel: str) -> Iterator[str]:
    """Slab paging runs ONLY on the async page thread: calls to
    ``.rebalance(`` / ``.fold_accesses(`` outside serving/paging.py are
    flagged — each is a batched slab gather + device upload that would
    stall every in-flight query if it ran on a serve path. Tests and
    benches (outside the package) drive paging deterministically and
    are exempt; a deliberate in-package call site can carry
    ``# lint: ok``."""
    if not rel.startswith("predictionio_tpu/") or rel in _PAGER_FILES:
        return
    lines = text.splitlines()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        if not (isinstance(fn, ast.Attribute)
                and fn.attr in ("rebalance", "fold_accesses")):
            continue
        line = lines[node.lineno - 1] if node.lineno <= len(lines) else ""
        if "# lint: ok" in line:
            continue
        yield (f"{rel}:{node.lineno}: .{fn.attr}() belongs on the async "
               "page thread (serving/paging.PageManager); a slab "
               "promotion on a serve path stalls every query behind a "
               "device upload — or mark '# lint: ok' for a "
               "pager-driven context")


def _check_training_reads(tree: ast.AST, text: str,
                          rel: str) -> Iterator[str]:
    """In models/: a ``read_training`` that iterates Events via
    ``store.find_events(`` walks the slow object path — per-frame
    Event + datetime + DataMap construction — instead of the columnar
    ingest pipeline (``store.rating_columns`` / ``store.pair_columns``
    or ``EventStore.scan_columns``), which is several times faster and
    prepared-data cached. Serving-time reads (``find_by_entity``) and
    property aggregation are fine. ``# lint: ok`` on the line is the
    escape hatch for genuinely event-shaped training data."""
    if not rel.startswith(_MODELS_DIRS):
        return
    lines = text.splitlines()
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                or node.name != "read_training":
            continue
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Call):
                continue
            fn = sub.func
            if not (isinstance(fn, ast.Attribute)
                    and fn.attr == "find_events"
                    and isinstance(fn.value, ast.Name)
                    and fn.value.id == "store"):
                continue
            line = lines[sub.lineno - 1] if sub.lineno <= len(lines) else ""
            if "# lint: ok" in line:
                continue
            yield (f"{rel}:{sub.lineno}: store.find_events() in "
                   "read_training materializes Events on the training "
                   "path; use the columnar store.rating_columns/"
                   "pair_columns (or mark '# lint: ok')")


def _check_streaming_accumulation(tree: ast.AST, text: str,
                                  rel: str) -> Iterator[str]:
    """In streaming/: forbid ``.append(``/``.extend(`` on a name bound
    at module scope. The Refresher ticks every PIO_REFRESH_INTERVAL_S
    for the life of the server process, so any per-tick push into
    process-lifetime state is an unbounded memory leak that only shows
    up days into a deploy. Per-tick lists are fine (they die with the
    tick); a genuinely bounded module-level accumulator (ring buffer,
    capped dedup set) is marked ``# lint: ok`` on the line."""
    if not rel.startswith(_STREAMING_DIRS):
        return
    module_names = set()
    for node in tree.body if isinstance(tree, ast.Module) else []:
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    module_names.add(t.id)
        elif isinstance(node, ast.AnnAssign) \
                and isinstance(node.target, ast.Name):
            module_names.add(node.target.id)
    if not module_names:
        return
    lines = text.splitlines()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        if not (isinstance(fn, ast.Attribute)
                and fn.attr in ("append", "extend")
                and isinstance(fn.value, ast.Name)
                and fn.value.id in module_names):
            continue
        line = lines[node.lineno - 1] if node.lineno <= len(lines) else ""
        if "# lint: ok" in line:
            continue
        yield (f"{rel}:{node.lineno}: .{fn.attr}() into module-level "
               f"'{fn.value.id}' in a streaming hot loop accumulates "
               "without bound across refresh ticks; keep per-tick state "
               "tick-local, or mark a bounded accumulator '# lint: ok'")


def _check_hot_route(tree: ast.AST, text: str, rel: str) -> Iterator[str]:
    """On the serve wire hot route (serving/server.py ``_fast_*``
    functions and the wire.py framing/service loop): forbid per-request
    ``json.dumps(``/``json.loads(`` and dict-literal/comprehension
    construction. The selector wire's whole throughput win is that the
    per-request path touches no generic JSON codec and allocates no
    header/result dicts — a regression here silently re-serializes the
    route the bench gates. Explicit ``dict(...)`` constructor calls
    pass (rare, visible); ``# lint: ok`` on the line is the escape
    hatch for documented fallbacks."""
    if rel not in _HOT_ROUTE_FILES:
        return
    lines = text.splitlines()

    def escaped(lineno: int) -> bool:
        line = lines[lineno - 1] if lineno <= len(lines) else ""
        return "# lint: ok" in line

    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if not (node.name.startswith("_fast")
                or node.name in _HOT_ROUTE_FUNCS):
            continue
        for sub in ast.walk(node):
            if isinstance(sub, (ast.Dict, ast.DictComp)):
                if escaped(sub.lineno):
                    continue
                kind = ("dict literal" if isinstance(sub, ast.Dict)
                        else "dict comprehension")
                yield (f"{rel}:{sub.lineno}: {kind} in hot-route "
                       f"'{node.name}' allocates per request; splice "
                       "pre-encoded fragments or scan in place (or "
                       "mark '# lint: ok')")
            elif isinstance(sub, ast.JoinedStr):
                if escaped(sub.lineno):
                    continue
                yield (f"{rel}:{sub.lineno}: f-string in hot-route "
                       f"'{node.name}' formats per request; splice "
                       "pre-encoded fragments (or mark '# lint: ok' "
                       "for an error/fallback path)")
            elif isinstance(sub, ast.Call) \
                    and isinstance(sub.func, ast.Attribute) \
                    and sub.func.attr in ("dumps", "loads") \
                    and isinstance(sub.func.value, ast.Name) \
                    and sub.func.value.id == "json":
                if escaped(sub.lineno):
                    continue
                yield (f"{rel}:{sub.lineno}: json.{sub.func.attr}() in "
                       f"hot-route '{node.name}' re-serializes the "
                       "wire path; use the compiled shape match / "
                       "pre-encoded fragments (or mark '# lint: ok' "
                       "for a documented fallback)")
            elif isinstance(sub, ast.Call) \
                    and isinstance(sub.func, ast.Attribute) \
                    and isinstance(sub.func.value, ast.Name) \
                    and sub.func.value.id == "trace" \
                    and sub.func.attr not in _HOT_TRACE_API:
                if escaped(sub.lineno):
                    continue
                yield (f"{rel}:{sub.lineno}: trace.{sub.func.attr}() in "
                       f"hot-route '{node.name}' is outside the "
                       "stamp-only API; hot paths may only write "
                       "preallocated stamp slots "
                       f"({', '.join(_HOT_TRACE_API)}) — "
                       "materialization runs in on_sent (or mark "
                       "'# lint: ok')")


def _tenant_named(node: ast.AST,
                  fragments=_TENANT_NAME_FRAGMENTS) -> str:
    """The tenant-suggesting name behind an expression, or ''."""
    name = ""
    if isinstance(node, ast.Name):
        name = node.id
    elif isinstance(node, ast.Attribute):
        name = node.attr
    low = name.lower()
    return name if any(f in low for f in fragments) else ""


def _check_tenant_growth(tree: ast.AST, text: str,
                         rel: str) -> Iterator[str]:
    """In tenancy/ and serving/: forbid raw growth of tenant-keyed
    containers — ``x[key] = v`` subscript assignment or
    ``.setdefault(`` on any name containing ``tenant``/``lane``. Each
    entry is state held per remote principal: a client cycling access
    keys (or a router fronting thousands of apps) makes it grow for
    the process lifetime. The sanctioned shapes are the LRU-capped
    ``tenancy.admission.BoundedTenantMap`` and the lane map inside
    ``tenancy.drr.DRRQueue`` (evicts idle lanes past its cap); a write
    whose bound is enforced elsewhere is marked ``# lint: ok`` on the
    line. In `_APP_KEYED_FILES` (the quality accumulators) the rule
    additionally keys on ``app``-named containers — the serve-path app
    label is minted by remote principals too."""
    app_keyed = rel in _APP_KEYED_FILES
    if not (rel.startswith(_TENANCY_DIRS) or app_keyed):
        return
    fragments = (_TENANT_NAME_FRAGMENTS + ("app",) if app_keyed
                 else _TENANT_NAME_FRAGMENTS)
    lines = text.splitlines()

    def escaped(lineno: int) -> bool:
        line = lines[lineno - 1] if lineno <= len(lines) else ""
        return "# lint: ok" in line

    for node in ast.walk(tree):
        if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            for t in targets:
                if not isinstance(t, ast.Subscript):
                    continue
                name = _tenant_named(t.value, fragments)
                if not name or escaped(node.lineno):
                    continue
                yield (f"{rel}:{node.lineno}: subscript-assign into "
                       f"tenant-keyed '{name}' grows per-principal "
                       "state without bound; use a capped map "
                       "(tenancy.admission.BoundedTenantMap) or mark "
                       "an externally-bounded write '# lint: ok'")
        elif isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr == "setdefault":
            name = _tenant_named(node.func.value, fragments)
            if not name or escaped(node.lineno):
                continue
            yield (f"{rel}:{node.lineno}: .setdefault() into "
                   f"tenant-keyed '{name}' grows per-principal state "
                   "without bound; use a capped map "
                   "(tenancy.admission.BoundedTenantMap) or mark an "
                   "externally-bounded write '# lint: ok'")


# the disaggregated ingest service: the whole point of the tier is
# bounded streaming, so whole-store materialization is design-breaking
_INGEST_SERVICE_FILES = ("predictionio_tpu/ingest/service.py",)


def _check_ingest_materialization(tree: ast.AST, text: str,
                                  rel: str) -> Iterator[str]:
    """In ingest/service.py: forbid whole-store materialization on the
    serving hot paths — ``.find(``/``find_events(`` (the Event-object
    walk) anywhere, and ``.scan_columns(`` unless the call line carries
    a ``# block-budget:`` marker naming the bound that slices the
    result into blocks before it leaves the tier. The service exists to
    stream bounded column blocks; an unmarked full materialization here
    silently reintroduces the per-consumer RSS spike the tier removes.
    ``# lint: ok`` also escapes, for non-hot admin paths."""
    if rel not in _INGEST_SERVICE_FILES:
        return
    lines = text.splitlines()

    def line(n: int) -> str:
        return lines[n - 1] if n <= len(lines) else ""

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        attr = node.func.attr if isinstance(node.func, ast.Attribute) \
            else (node.func.id if isinstance(node.func, ast.Name) else "")
        if "# lint: ok" in line(node.lineno):
            continue
        if attr in ("find", "find_events"):
            yield (f"{rel}:{node.lineno}: '{attr}(' walks Event "
                   "objects for the whole store inside the ingest "
                   "service; stream column blocks instead")
        elif attr == "scan_columns" and \
                "# block-budget:" not in line(node.lineno):
            yield (f"{rel}:{node.lineno}: 'scan_columns(' without a "
                   "'# block-budget:' marker — the ingest service must "
                   "slice every scan into bounded blocks before "
                   "streaming; name the budget on the call line")


def check_file(path: Path, root: Path) -> List[str]:
    rel = path.relative_to(root).as_posix()
    text = path.read_text()
    out: List[str] = []
    try:
        tree = ast.parse(text, filename=rel)
    except SyntaxError as e:
        return [f"{rel}:{e.lineno}: syntax error: {e.msg}"]
    if not (tree.body and isinstance(tree.body[0], ast.Expr)
            and isinstance(tree.body[0].value, ast.Constant)
            and isinstance(tree.body[0].value.value, str)):
        out.append(f"{rel}:1: missing module docstring")
    out.extend(_check_imports(tree, rel))
    out.extend(_check_defaults(tree, rel))
    out.extend(_check_excepts(tree, rel))
    out.extend(_check_lines(text, rel))
    out.extend(_check_instrumentation(tree, text, rel))
    out.extend(_check_bounded_waits(tree, text, rel))
    out.extend(_check_thread_names(tree, text, rel))
    out.extend(_check_urlopen_timeout(tree, text, rel))
    out.extend(_check_storage_writes(tree, text, rel))
    out.extend(_check_device_transfers(tree, text, rel))
    out.extend(_check_pager_thread(tree, text, rel))
    out.extend(_check_training_reads(tree, text, rel))
    out.extend(_check_streaming_accumulation(tree, text, rel))
    out.extend(_check_hot_route(tree, text, rel))
    out.extend(_check_tenant_growth(tree, text, rel))
    out.extend(_check_ingest_materialization(tree, text, rel))
    return out


def run(root: Path) -> List[str]:
    """Lint every package + top-level source file; returns violations."""
    targets: List[Path] = []
    for sub in ("predictionio_tpu", "tests"):
        d = root / sub
        if d.exists():
            targets.extend(p for p in sorted(d.rglob("*.py"))
                           if "_build" not in p.parts)
    for top in ("bench.py", "__graft_entry__.py"):
        p = root / top
        if p.exists():
            targets.append(p)
    out: List[str] = []
    for path in targets:
        rel = path.relative_to(root).as_posix()
        if rel in EXEMPT:
            continue
        out.extend(check_file(path, root))
    return out


def main() -> int:
    root = Path(__file__).resolve().parents[2]
    violations = run(root)
    for v in violations:
        print(v)
    print(f"lint: {len(violations)} violation(s)")
    return 1 if violations else 0


if __name__ == "__main__":
    sys.exit(main())
