"""Compile caches: jax's persistent HLO cache + the paddle_tpu AOT
artifact cache.

TPU compiles are expensive (tens of seconds for a ResNet-50 train step),
and every process start pays them again: serving warmup re-traces its
whole bucket lattice, a trainer restarting after a rollback re-compiles
the very step it just ran, and a chip run on a fresh machine spends its
time budget compiling instead of measuring. Two layers attack that:

1. ``enable_persistent_cache`` — jax's own persistent compilation cache
   (HLO + compile options -> executable), placed by ONE rule: at
   ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it, else at
   ``<checkout>/.jax_cache``. Kills the XLA *backend compile*, but a
   fresh process still pays the full Python trace and lowering of every
   program.

2. The **AOT artifact cache** (this module's main export): serialized
   *compiled executables* (``jax.experimental.serialize_executable``)
   keyed by the same signature the executors' in-process jit cache
   already computes — program CONTENT hash + feed/fetch signature +
   ``(K, fetch_reduce, unroll, stacked-feeds)`` + trace-time env flags +
   device/platform + jax version. A warm process start skips trace,
   lowering AND compile: one disk read, one deserialize, dispatch.

Integrity model (the checkpoint/snapshot.py discipline): entries are
written into a ``.tmp_*.<pid>`` directory with per-file fsync, published
by ONE ``os.rename``, and carry sha256 hashes of the payload in
``meta.json``; loads re-hash before deserializing, so a torn or
bit-flipped entry is SKIPPED WITH A WARNING and the caller falls back to
a fresh compile — never a half-loaded executable. The deserialization
itself is a pickle (jax's wire format), which is why the hash check is
mandatory and a shared cache dir must be trusted like the checkpoint
root: whoever can write it can execute code in your process.

Enable with FLAGS_aot_cache_dir=<dir> or ``enable_aot_cache()``
(ptpu_serve defaults it on; the test suite leaves it off — CPU compiles
are cheap and test isolation matters more). '' is the explicit off
switch. The reference era had no counterpart: its op-by-op executor had
nothing to cache.
"""
import hashlib
import json
import os
import pickle
import shutil
import time
import warnings

# 2: the step returns (new_state, fetches, errors), state_rw first
# (lowering.jit_step); an executable stored under 1 unpacks wrongly
AOT_FORMAT_VERSION = 2
AOT_ENTRY_PREFIX = "aot_"
AOT_TMP_PREFIX = ".tmp_aot_"
META_FILE = "meta.json"
PAYLOAD_FILE = "payload.bin"
TREES_FILE = "trees.pkl"

_aot_default_dir = None
_warned = set()

# always-on counters (the profiler's per-tag view needs an active
# profiler; the subprocess tests read these instead)
_aot_stats = {"hits": 0, "misses": 0, "stores": 0, "store_errors": 0,
              "load_errors": 0, "saved_s": 0.0}


def aot_stats():
    """Snapshot of the process-wide AOT cache counters: hits (disk loads
    that replaced a compile), misses (keys with no usable entry), stores
    (entries published by this process), load_errors (corrupt/stale
    entries skipped), store_errors, saved_s (recorded compile seconds
    avoided, net of deserialize time)."""
    return dict(_aot_stats)


def reset_aot_stats():
    for k in _aot_stats:
        _aot_stats[k] = 0.0 if k == "saved_s" else 0


def _warn_once(key, message):
    """One warning per distinct failure site per process: a cache is an
    optimization and must not spam, but a silently swallowed enable
    failure (the pre-PR-6 behavior) means nobody learns the cache was
    off until the bench numbers look wrong."""
    if key in _warned:
        return
    _warned.add(key)
    warnings.warn(message, RuntimeWarning, stacklevel=3)


def repo_cache_dir():
    """``<checkout>/.jax_cache`` (git-ignored): a path fixed by the
    checkout, never by the temp dir, a pid or the time — the directory
    is part of the cache's identity, so one that moves never hits."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, ".jax_cache")


def enable_persistent_cache():
    """Place jax's persistent compilation cache and return its directory.

    The one rule, and the tree's only ``jax_compilation_cache_dir``
    update: when ``JAX_COMPILATION_CACHE_DIR`` is set jax has already
    read it at import and nothing is set here; when it is not, the cache
    goes to ``repo_cache_dir()``. Idempotent; entry points
    (chip_smoke.py, the tools) call it once before their first compile."""
    import jax
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    path = repo_cache_dir()
    if jax.config.jax_compilation_cache_dir != path:
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
        # cache even fast compiles: a run is dozens of sub-second jits
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


# ------------------------------------------- what a first run waited for
# jax.monitoring reports a duration when something compiles and never on
# a warm call: the trace of a jitted function to a jaxpr (the program's
# lowering rules run under it), the jaxpr's lowering to StableHLO, and
# `backend_compile_duration`, which is the XLA compile OR the persistent
# cache's read and load, whole; on a hit the cache's retrieval time is
# reported too and lies inside it.
_COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile_or_load",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read",
}
_watching = False


def _on_duration(event, seconds, **_):
    phase = _COMPILE_PHASES.get(event)
    if phase is None:
        return
    from .dispatch import open_step
    step = open_step()
    if step is None:    # the caller's own jit, not an executor's dispatch
        return
    now = time.perf_counter()
    step.compile_events.append((phase, now - seconds, now,
                                step.innermost()))


def watch_compile_phases():
    """Register, once a process, the listener that collects the compile
    phases of an executor's dispatch (`book_compile_phases`)."""
    global _watching
    if _watching:
        return
    _watching = True
    import jax
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def outermost_phases(events):
    """Of one step's `(phase, start, end, span)` events, those that count.
    A jit inside the step's trace (a Pallas wrapper's, an op evaluated
    eagerly on constants) reports phases of its own INSIDE the interval
    of the phase that called it, before that one reports: an event that
    lies within another's interval, whatever the two phases, is part of
    that one's seconds already and is left out. `cache_read` is the
    exception by design: it is kept wherever the `compile_or_load` around
    it is, and read beside it, never added to it."""
    main = sorted((e for e in events if e[0] != "cache_read"),
                  key=lambda e: (e[1], -e[2]))
    kept = []
    for e in main:
        if not kept or e[2] > kept[-1][2]:
            kept.append(e)
    reads = [e for e in events if e[0] == "cache_read" and not any(
        k[0] != "compile_or_load" and k[1] <= e[1] and e[2] <= k[2]
        for k in kept)]
    return kept, reads


def book_compile_phases(events):
    """Book what one exec/step waited for: each phase as a child of the
    exec/* span that was innermost when it was reported (`jax/trace`,
    `jax/lower`, `jax/compile_or_load` with `cache_hit`), and into
    `ptpu_compile_phase_seconds_total{phase}` with
    `ptpu_compile_phase_events_total{phase}` beside it."""
    from ..observability.registry import REGISTRY
    seconds = REGISTRY.counter(
        "ptpu_compile_phase_seconds_total",
        "seconds an executor's dispatch waited for jax, by phase: trace "
        "(the lowering rules under jax's trace), lower (jaxpr to "
        "StableHLO), compile_or_load (XLA compile, or the persistent "
        "cache's read and load), cache_read (inside compile_or_load)")
    count = REGISTRY.counter(
        "ptpu_compile_phase_events_total",
        "phases counted into ptpu_compile_phase_seconds_total")
    kept, reads = outermost_phases(events)
    for phase, t0, t1, span in kept + reads:
        seconds.inc(t1 - t0, phase=phase)
        count.inc(phase=phase)
        if phase == "cache_read":
            continue
        args = {}
        if phase == "compile_or_load":
            args["cache_hit"] = any(t0 <= r[1] and r[2] <= t1
                                    for r in reads)
        span.child_at("jax/" + phase, t0, t1, **args)


# ------------------------------------------------------ AOT artifact cache
def enable_aot_cache():
    """Default the AOT artifact cache on, in an ``aot`` directory inside
    the persistent cache dir (so both caches are placed by the one
    rule above). FLAGS_aot_cache_dir, re-read on every dispatch, still
    wins when set ('' = explicit off)."""
    global _aot_default_dir
    _aot_default_dir = os.path.join(enable_persistent_cache(), "aot")
    return active_aot_cache_dir()


def active_aot_cache_dir():
    """The AOT cache dir in effect for the next dispatch, or None (off).
    FLAGS_aot_cache_dir is re-read every call ('' = explicit off) so
    tests and tools can toggle it without process-global state; the
    enable_aot_cache default applies only while the flag is unset."""
    if "FLAGS_aot_cache_dir" in os.environ:
        return os.environ["FLAGS_aot_cache_dir"] or None
    return _aot_default_dir


# -- key schema ----------------------------------------------------------
_program_hash_cache = {}  # (program uid, version) -> content sha256


def program_content_hash(program):
    """sha256 of the program's serialized desc (core/program_desc bytes)
    — the cross-process identity the in-process (uid, version) key can't
    provide: uids are per-process counters, but two processes building
    the same model byte-for-byte produce the same desc. Returns None
    (warn once) for programs the desc format can't serialize; those fall
    back to the in-process cache only."""
    key = (program._uid, program._version)
    got = _program_hash_cache.get(key)
    if got is not None:
        return got
    try:
        from .program_desc import program_to_bytes
        digest = hashlib.sha256(program_to_bytes(program)).hexdigest()
    except Exception as e:
        _warn_once("program-hash:%s" % type(e).__name__,
                   "program is not serializable (%s: %s); the AOT "
                   "artifact cache is skipped for it (in-process jit "
                   "cache still applies)" % (type(e).__name__, e))
        return None
    if len(_program_hash_cache) > 256:
        _program_hash_cache.clear()
    _program_hash_cache[key] = digest
    return digest


def _jsonable(v):
    """Canonicalize key-material values for hashing: tuples/lists
    recurse, None/str/bool/int/float pass through, anything else (e.g. a
    PartitionSpec) stringifies via repr — stable within a jax version,
    which the key already pins."""
    if isinstance(v, (tuple, list)):
        return [_jsonable(x) for x in v]
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in sorted(v.items())}
    if v is None or isinstance(v, (str, bool, int, float)):
        return v
    return repr(v)


def aot_entry_key(program, feed_sig, fetch_names, trace_env, multi,
                  device, extra=None):
    """Build the persistent cache key for one executor dispatch.

    Returns (key_hash, key_material) or None when the program has no
    content hash. key_material is the full human-readable dict recorded
    in the entry's meta.json (ptpu_cache inspect shows it); key_hash is
    sha256 over its canonical JSON. Everything that shapes the compiled
    artifact is in here — see ARCHITECTURE.md §18 for the schema:

      * format version (schema changes invalidate everything),
      * jax version (serialized executables are not portable across it),
      * platform + device kind + device count (an artifact compiled for
        one chip topology must never load on another),
      * program content hash (any program edit re-keys),
      * feed signature, fetch names,
      * trace-time env flags (lowering.trace_env_key),
      * the multi-step tuple (K, fetch_reduce, unroll, stacked feeds),
      * extra: caller-specific config (ParallelExecutor's mesh + param
        shardings).
    """
    prog_hash = program_content_hash(program)
    if prog_hash is None:
        return None
    import jax
    material = {
        "format_version": AOT_FORMAT_VERSION,
        "jax_version": jax.__version__,
        "platform": getattr(device, "platform", str(device)),
        "device_kind": getattr(device, "device_kind", ""),
        # device IDENTITY, not just kind: serialize_executable binds an
        # artifact to the concrete devices it was compiled for, and
        # deserialize_and_load rebinds to exactly those — an artifact
        # compiled on chip 0 (or mesh span [0,1]) called with arrays on
        # chip 2 (span [2,3]) fails at call time with a sharding
        # mismatch whose reprs look identical (found by the tp=2
        # 2-replica pool: replica 1 loaded replica 0's artifact).
        # Multi-device spans additionally ride extra["mesh_device_ids"].
        "device_id": getattr(device, "id", None),
        "num_devices": 1 if extra is None else extra.get("num_devices", 1),
        "program_sha256": prog_hash,
        "program_random_seed": int(getattr(program, "random_seed", 0) or 0),
        "feed_sig": _jsonable(feed_sig),
        "fetch_names": _jsonable(tuple(fetch_names)),
        "trace_env": _jsonable(trace_env),
        "multi": _jsonable(multi),
        "extra": _jsonable(extra or {}),
    }
    blob = json.dumps(material, sort_keys=True).encode("utf-8")
    return hashlib.sha256(blob).hexdigest(), material


def entry_dir(cache_dir, key_hash):
    return os.path.join(cache_dir, AOT_ENTRY_PREFIX + key_hash)


# -- write protocol (checkpoint/snapshot.py fsync+rename discipline,
#    one shared implementation in core/utils.py) --------------------------
from .utils import fsync_dir as _fsync_dir              # noqa: E402
from .utils import write_bytes_fsync as _write_bytes    # noqa: E402


def aot_store(cache_dir, key_hash, key_material, compiled,
              compile_seconds):
    """Serialize one compiled executable into the cache, atomically.

    Best-effort by contract: every failure warns once — a full disk or
    an unwritable dir must never fail the training step that just
    compiled successfully. The entry is INVISIBLE until one os.rename
    publishes it (no torn reads), and meta.json records the sha256 of
    both artifact files plus the compile seconds this process paid —
    the number a later process's profiler reports as time saved.

    Returns True when the artifact is AVAILABLE on disk afterwards
    (published by this process, or a racing process published the same
    key — either way a restart will load it); False only on real
    failure, which the caller uses to decide the donation tradeoff
    (no artifact = no reason to keep the donation-free executable)."""
    try:
        from jax.experimental import serialize_executable
        payload, in_tree, out_tree = serialize_executable.serialize(
            compiled)
        trees = pickle.dumps((in_tree, out_tree))
        os.makedirs(cache_dir, exist_ok=True)
        final = entry_dir(cache_dir, key_hash)
        if os.path.isdir(final):
            return True  # another process already published this key
        tmp = os.path.join(cache_dir, "%s%s.%d"
                           % (AOT_TMP_PREFIX, key_hash, os.getpid()))
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)
        _write_bytes(os.path.join(tmp, PAYLOAD_FILE), payload)
        _write_bytes(os.path.join(tmp, TREES_FILE), trees)
        meta = {
            "format_version": AOT_FORMAT_VERSION,
            "key_hash": key_hash,
            "key": key_material,
            "payload_sha256": hashlib.sha256(payload).hexdigest(),
            "trees_sha256": hashlib.sha256(trees).hexdigest(),
            "payload_bytes": len(payload),
            "compile_seconds": float(compile_seconds),
            "created_at": time.time(),
        }
        _write_bytes(os.path.join(tmp, META_FILE),
                     json.dumps(meta, indent=1, sort_keys=True)
                     .encode("utf-8"))
        _fsync_dir(tmp)
        try:
            os.rename(tmp, final)  # the commit point
        except OSError:
            shutil.rmtree(tmp, ignore_errors=True)
            return os.path.isdir(final)  # lost the race = still cached
        _fsync_dir(cache_dir)
        _aot_stats["stores"] += 1
        return True
    except Exception as e:  # noqa: BLE001 — cache writes are best-effort
        _aot_stats["store_errors"] += 1
        _warn_once("aot-store:%s" % type(e).__name__,
                   "could not store an AOT compile artifact in %r (%s: "
                   "%s); compiles will not be reusable across processes"
                   % (cache_dir, type(e).__name__, e))
        return False


def _entry_problems(path, key_material=None, deep=True):
    """Verification shared by loads and `ptpu_cache verify`: returns a
    list of problem strings (empty = entry is loadable). deep=False
    skips the payload re-hash (structure + metadata only)."""
    problems = []
    meta_path = os.path.join(path, META_FILE)
    try:
        with open(meta_path, "rb") as f:
            meta = json.loads(f.read().decode("utf-8"))
    except (OSError, ValueError) as e:
        return ["meta.json unreadable: %s" % e]
    if meta.get("format_version") != AOT_FORMAT_VERSION:
        problems.append("format_version %r != %d"
                        % (meta.get("format_version"), AOT_FORMAT_VERSION))
    if key_material is not None and meta.get("key") != _jsonable(
            key_material):
        # hash collision or a hand-edited entry: either way, not ours
        problems.append("recorded key material does not match the "
                        "requested key")
    for fname, hkey in ((PAYLOAD_FILE, "payload_sha256"),
                        (TREES_FILE, "trees_sha256")):
        fpath = os.path.join(path, fname)
        if not os.path.exists(fpath):
            problems.append("%s missing" % fname)
            continue
        if not deep:
            continue
        h = hashlib.sha256()
        try:
            with open(fpath, "rb") as f:
                for chunk in iter(lambda: f.read(1 << 20), b""):
                    h.update(chunk)
        except OSError as e:
            problems.append("%s unreadable: %s" % (fname, e))
            continue
        if h.hexdigest() != meta.get(hkey):
            problems.append("%s sha256 mismatch (bit flip or torn "
                            "write)" % fname)
    return problems


def read_entry_meta(path):
    with open(os.path.join(path, META_FILE), "rb") as f:
        return json.loads(f.read().decode("utf-8"))


def aot_load(cache_dir, key_hash, key_material, devices):
    """Load one entry: hash-verify, deserialize onto `devices` (the
    Executor's place device; the mesh's devices, in mesh order, for
    ParallelExecutor — the same identities the key records), return
    (compiled_executable, seconds_saved) — or None on miss/corruption
    (the caller compiles fresh; that fallback is the cache's ONLY
    failure mode).

    A *stale* entry cannot be reached from here: jax version, device
    kind and format version are inside the hashed key, so a changed
    environment computes a different key_hash and simply misses. What
    this function defends against is the same-key entry whose BYTES are
    wrong — torn write, bit flip, hand edit — which the sha256 check
    catches before any byte reaches pickle. Corrupt entries are removed
    (best-effort) so the fresh compile can re-publish the slot."""
    path = entry_dir(cache_dir, key_hash)
    if not os.path.isdir(path):
        _aot_stats["misses"] += 1
        return None
    t0 = time.perf_counter()
    problems = _entry_problems(path, key_material=key_material, deep=True)
    if problems:
        _aot_stats["load_errors"] += 1
        _warn_once("aot-corrupt:%s" % key_hash[:16],
                   "AOT cache entry %s is not loadable (%s); skipping it "
                   "and compiling fresh" % (path, "; ".join(problems)))
        shutil.rmtree(path, ignore_errors=True)
        return None
    try:
        meta = read_entry_meta(path)
        with open(os.path.join(path, PAYLOAD_FILE), "rb") as f:
            payload = f.read()
        with open(os.path.join(path, TREES_FILE), "rb") as f:
            in_tree, out_tree = pickle.loads(f.read())
        from jax.experimental import serialize_executable
        # without execution_devices the executable binds to EVERY
        # local device of the DEFAULT backend and rejects single-device
        # arguments ("expected N shards")
        devices = list(devices)
        compiled = serialize_executable.deserialize_and_load(
            payload, in_tree, out_tree, backend=devices[0].client,
            execution_devices=devices)
    except Exception as e:  # noqa: BLE001 — fall back to a fresh compile
        _aot_stats["load_errors"] += 1
        _warn_once("aot-load:%s" % type(e).__name__,
                   "AOT cache entry %s failed to deserialize (%s: %s); "
                   "skipping it and compiling fresh"
                   % (path, type(e).__name__, e))
        shutil.rmtree(path, ignore_errors=True)
        return None
    load_s = time.perf_counter() - t0
    saved = max(0.0, float(meta.get("compile_seconds") or 0.0) - load_s)
    _aot_stats["hits"] += 1
    _aot_stats["saved_s"] += saved
    return compiled, saved


def discard_bad_entry(cache_dir, key_hash, reason):
    """An executable that failed AT CALL TIME (argument avals rejected)
    despite a verified entry on disk: count a load error (any earlier
    hit count stands — the load itself succeeded), warn once, and
    remove the entry so the fresh compile re-publishes the slot."""
    _aot_stats["load_errors"] += 1
    _warn_once("aot-call:%s" % key_hash[:16],
               "AOT cache entry %s loaded but was unusable (%s); "
               "discarded, compiling fresh"
               % (entry_dir(cache_dir, key_hash), reason))
    shutil.rmtree(entry_dir(cache_dir, key_hash), ignore_errors=True)


# -- maintenance (ptpu_cache CLI) ----------------------------------------
def list_entries(cache_dir):
    """[(entry_path, meta_or_None)] for every published entry, newest
    first by created_at (unreadable meta -> None, still listed so verify
    and gc see torn entries)."""
    if not os.path.isdir(cache_dir):
        return []
    out = []
    for name in os.listdir(cache_dir):
        if not name.startswith(AOT_ENTRY_PREFIX):
            continue
        path = os.path.join(cache_dir, name)
        if not os.path.isdir(path):
            continue
        try:
            meta = read_entry_meta(path)
        except (OSError, ValueError):
            meta = None
        out.append((path, meta))
    out.sort(key=lambda pm: (pm[1] or {}).get("created_at", 0.0),
             reverse=True)
    return out


def verify_entry(path):
    """Deep-verify one entry; list of problems (empty = ok)."""
    return _entry_problems(path, deep=True)


def entry_size_bytes(path):
    total = 0
    for name in os.listdir(path):
        try:
            total += os.path.getsize(os.path.join(path, name))
        except OSError:
            pass
    return total


def _pid_alive(pid):
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        return True  # alive under another uid — not ours to sweep
    except OSError:
        return True
    return True


def clean_stale_tmp(cache_dir):
    """Sweep dead writers' unpublished tmp dirs (the checkpoint
    clean_stale_tmp rule: only entries with a parseable pid suffix whose
    pid is dead; EPERM counts as alive)."""
    removed = []
    if not os.path.isdir(cache_dir):
        return removed
    for name in os.listdir(cache_dir):
        if not name.startswith(AOT_TMP_PREFIX):
            continue
        pid_part = name.rsplit(".", 1)[-1]
        if not pid_part.isdigit() or _pid_alive(int(pid_part)):
            continue
        path = os.path.join(cache_dir, name)
        shutil.rmtree(path, ignore_errors=True)
        removed.append(path)
    return removed


def gc_aot_cache(cache_dir, max_age_days=None, max_total_mb=None,
                 dry_run=False):
    """Retention for the artifact cache, reusing the checkpoint
    discipline: age window first (entries older than max_age_days go),
    then a size budget (newest entries kept until max_total_mb is
    spent, LRU-by-created_at beyond it). Returns (doomed_paths,
    kept_paths); with dry_run nothing is deleted. Stale tmp droppings
    are always swept (never in dry_run's doomed list — they were never
    published)."""
    entries = list_entries(cache_dir)
    now = time.time()
    doomed, kept = [], []
    budget = None if max_total_mb is None else max_total_mb * (1 << 20)
    spent = 0
    for path, meta in entries:  # newest first
        age_days = (now - (meta or {}).get("created_at", 0.0)) / 86400.0
        size = entry_size_bytes(path)
        if meta is None:
            doomed.append(path)  # unreadable meta: unloadable anyway
            continue
        if max_age_days is not None and age_days > max_age_days:
            doomed.append(path)
            continue
        if budget is not None and spent + size > budget:
            doomed.append(path)
            continue
        spent += size
        kept.append(path)
    if not dry_run:
        for path in doomed:
            shutil.rmtree(path, ignore_errors=True)
        clean_stale_tmp(cache_dir)
    return doomed, kept
