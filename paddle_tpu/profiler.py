"""Profiler.

Parity: python/paddle/fluid/profiler.py (cuda_profiler/profiler context
managers over platform::Profiler, whose report printed an Event table sorted
by `sorted_key` in {calls,total,max,min,ave}). Two tables take its place:

  entries     one row a jit entry (program, feed signature): calls, compiles,
              AOT hits, and in profiling mode host seconds behind a
              block_until_ready. One jitted XLA computation replaces the
              reference's per-op kernel stream, so the host sees entries.
  device ops  the reference's per-op Event table, on the device's clock: one
              row a fluid op type, read from the jax.profiler trace the
              profiler wrote (or any kept trace: `python -m paddle_tpu.profiler
              <trace dir>`). Every fluid op lowers inside a named scope
              (core/lowering.op_scope) and every Pallas kernel has a name
              (ops/pallas_kernels.KERNEL_NAMES); `device_op_table` sums each
              device operation's self time under the innermost fluid scope of
              its HLO op_name. A fusion belongs to its root instruction's
              scope. Off a TPU the trace has no device plane and the table
              is empty.

The same table without a trace file (PR 69): an executor describes, once a
compile, the arguments its step was compiled for (`note_step`); asked
(`step_op_names`), jax hands the loaded executable back for them without a
compile and its text says each instruction's op_name, so
`device_seconds_by_op` reduces {instruction: self seconds}, what the
benchmark's trace reduction keeps, through the same row key and maker. It
costs nothing until it is asked for. `--by scope` (or `by="scope"`) names a
row by the whole path of scopes: what a layer's time is made of.

Two smaller blocks: `profile_report` ends with the host seconds the lowering
rules took under jax's trace, by fluid op type (core/lowering.lower_op), and
`python -m paddle_tpu.profiler <trace dir>` with the device's idle gaps by
program span: the flight recorder's spans are `ptpu/...` annotations on the
trace's host plane (observability/trace.py), and each gap between device
operations is booked to the innermost of them open for most of it.
"""
import bisect
import contextlib
import glob
import os
import re
import threading
import time
import weakref

import jax

from .core.lowering import (PASS_MARK, SCOPE_MARK, parse_op_scope,
                            parse_pass_scope)
from .observability.trace import ANNOTATION_PREFIX

__all__ = ["profiler", "start_profiler", "stop_profiler", "reset_profiler",
           "profile_report", "record_event", "cache_stats", "note_sync",
           "sync_stats", "dispatch_path", "record_idle", "snapshot",
           "device_op_table", "device_op_table_from", "read_op_names",
           "note_step", "step_op_names", "device_seconds_by_op",
           "render_device_ops", "idle_gaps_by_span", "render_idle_gaps"]

_active = False
_trace_dir = None
_span = [None, None]
_entries = {}  # tag -> {"calls", "runs", "total", "max", "min",
#                        "compiles", "compile_s", "aot_hits", "saved_s",
#                        "idle_s", "gaps"}  (see record_run/record_idle)
_syncs = {}    # tag -> host-sync count (see note_sync)
_syncs_on_dispatch = 0  # syncs observed on a marked dispatch-path thread
_sync_lock = threading.Lock()  # note_sync is called from dispatch
# workers, completion threads and clients at once — an unlocked
# read-modify-write could lose exactly the dispatch-path increment the
# no-premature-sync regression tests exist to catch
_tls = threading.local()  # .dispatch_path: this thread IS a hot
# dispatch loop (serving batcher worker, training step loop) — any
# note_sync here is a premature sync the pipeline regression test fails


def is_active():
    return _active


def note_sync(tag):
    """Count one host<->device synchronization point (block_until_ready,
    np.asarray of a device array, watchdog completion wait). Every sync
    site on the runtime's dispatch paths calls this, tagged by WHY it
    synced — so "the device pipeline never stalls on the host" is a
    testable property (`sync_stats`), not a code-review hope. Counting
    is ALWAYS on (a dict increment at a site already paying a
    millisecond-class device wait — unlike timing, it needs no extra
    sync of its own, so it must not require the profiler's
    sync-everything mode). Syncs observed on a thread inside a
    `dispatch_path()` region additionally count as on-dispatch-path:
    the pipelined batcher/trainer regression tests assert that number
    stays zero."""
    global _syncs_on_dispatch
    with _sync_lock:
        _syncs[tag] = _syncs.get(tag, 0) + 1
        if getattr(_tls, "dispatch_path", False):
            _syncs_on_dispatch += 1


@contextlib.contextmanager
def dispatch_path():
    """Mark the current thread as a hot dispatch loop for the duration:
    any note_sync inside is a premature host sync (it stalls the next
    dispatch behind a D2H wait). The serving batcher's dispatch worker
    wraps each dispatch in this; tests wrap training step loops."""
    prev = getattr(_tls, "dispatch_path", False)
    _tls.dispatch_path = True
    try:
        yield
    finally:
        _tls.dispatch_path = prev


def sync_stats():
    """{"by_tag": {tag: count}, "total", "on_dispatch_path"} since the
    last reset_profiler(). Counting is always-on (see note_sync), so
    counts accumulate from process start across unprofiled traffic —
    call reset_profiler() to scope a measurement window."""
    with _sync_lock:
        return {"by_tag": dict(_syncs),
                "total": sum(_syncs.values()),
                "on_dispatch_path": _syncs_on_dispatch}


def record_idle(tag, idle_s):
    """Account `idle_s` seconds the device spent with no dispatch queued
    under `tag` (between one dispatch's completion and the next
    dispatch's enqueue). The serving InflightWindow's completion thread,
    which observes real completions, reports through here; the report's
    Idle(s)/Util% columns render it. The executors report none (their
    host clock behind a sync cannot see it): their rows read "-", and a
    training step's idle share comes from the device trace."""
    e = _entries.setdefault(tag, _fresh_entry())
    e["idle_s"] += idle_s
    e["gaps"] += 1


def _fresh_entry():
    return {"calls": 0, "runs": 0, "total": 0.0, "max": 0.0,
            "min": float("inf"), "compiles": 0, "compile_s": 0.0,
            "aot_hits": 0, "saved_s": 0.0, "idle_s": 0.0, "gaps": 0}


def record_run(tag, seconds, compiled=False, aot_hit=False, saved_s=0.0):
    """Executor hook: one jitted dispatch of `tag` took `seconds` (blocked).
    Calls that traced+compiled are counted separately (Compiles/Compile(s))
    so Total/Max/Min/Ave stay honest cache-hit execution times.

    aot_hit=True marks a call whose executable came from the persistent
    AOT artifact cache (core/compile_cache.py) instead of a fresh
    compile — still an execution call (the deserialize happens before
    the timed dispatch), but counted in its own column with `saved_s`,
    the compile seconds the recording process paid minus the load time,
    so warm-vs-cold process starts are visible per tag in one report."""
    e = _entries.setdefault(tag, _fresh_entry())
    e["calls"] += 1
    if aot_hit:
        e["aot_hits"] += 1
        e["saved_s"] += saved_s
    if compiled:
        e["compiles"] += 1
        e["compile_s"] += seconds
    else:
        e["runs"] += 1
        e["total"] += seconds
        e["max"] = max(e["max"], seconds)
        e["min"] = min(e["min"], seconds)


def cache_stats():
    """Aggregate compile-cache accounting over every profiled tag:
    {"compiles", "aot_hits", "warm_calls", "saved_s"} — compiles are
    fresh trace+compile calls, aot_hits replaced a compile with a disk
    load, warm_calls hit the in-process jit cache, saved_s totals the
    recorded compile time avoided. The cross-process cache tests assert
    "zero new compiles" on exactly this counter."""
    compiles = sum(e["compiles"] for e in _entries.values())
    aot_hits = sum(e.get("aot_hits", 0) for e in _entries.values())
    calls = sum(e["calls"] for e in _entries.values())
    return {"compiles": compiles, "aot_hits": aot_hits,
            "warm_calls": calls - compiles - aot_hits,
            "saved_s": sum(e.get("saved_s", 0.0)
                           for e in _entries.values())}


def record_event(tag, seconds=0.0):
    """Count a discrete runtime event into the Event table — the
    resilience supervisor tags every recovery action this way
    (`resilience/<fault>:<action>` rows), so one profile_report() shows
    training dispatches and fault handling side by side. `seconds` is
    the time the handler spent (0 for pure bookkeeping events)."""
    record_run(tag, seconds, compiled=False)


def snapshot():
    """Machine-readable export of everything the profiler tracks, in one
    dict: {"entries": {tag: {calls, runs, total, max, min, ave,
    compiles, compile_s, aot_hits, saved_s, idle_s, gaps}},
    "sync_stats": sync_stats(), "cache_stats": cache_stats(),
    "device_ops": the newest `device_op_table` or None}. This is
    the PUBLIC surface for the observability registry and the tests
    — nothing should read the private `_entries` dict (its
    "min" sentinel and optional keys are internal). Values are plain
    numbers (JSON-safe); `min` reads 0.0 for entries with no exec
    calls, matching the report."""
    entries = {}
    for tag, e in list(_entries.items()):
        d = {"calls": e["calls"], "runs": e["runs"],
             "total": e["total"], "max": e["max"],
             "min": 0.0 if e["min"] == float("inf") else e["min"],
             "ave": e["total"] / max(e["runs"], 1),
             "compiles": e["compiles"], "compile_s": e["compile_s"],
             "aot_hits": e.get("aot_hits", 0),
             "saved_s": e.get("saved_s", 0.0),
             "idle_s": e.get("idle_s", 0.0), "gaps": e.get("gaps", 0)}
        entries[tag] = d
    return {"entries": entries, "sync_stats": sync_stats(),
            "cache_stats": cache_stats(), "device_ops": _device_ops}


_SORT_KEYS = ("calls", "total", "max", "min", "ave")


def _check_sorted_key(sorted_key):
    if sorted_key is not None and sorted_key not in _SORT_KEYS:
        raise ValueError("sorted_key must be one of %s, got %r"
                         % (list(_SORT_KEYS), sorted_key))


@contextlib.contextmanager
def profiler(state="All", sorted_key=None, profile_path="/tmp/profile"):
    """Parity: fluid.profiler.profiler context manager. state accepted for
    API compatibility (CPU/GPU/All — one device stream on TPU)."""
    _check_sorted_key(sorted_key)  # fail before the workload, not after
    start_profiler(state, profile_path)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


def start_profiler(state="All", profile_path="/tmp/profile"):
    global _trace_dir, _active
    _active = True
    _trace_dir = profile_path
    try:
        jax.profiler.start_trace(profile_path)
    except Exception:
        _trace_dir = None
    _span[0] = time.time()


def profile_report(sorted_key=None, json=False):
    """The Event-table equivalent: one row per jitted program entry.

    sorted_key: None (insertion order) | 'calls' | 'total' | 'max' | 'min'
    | 'ave' (reference profiler.py sorted_key contract).

    json=True returns the `snapshot()` dict instead of the rendered
    table — the machine-readable contract the
    observability registry consumes (sorted_key is still validated but
    irrelevant: consumers sort their own views)."""
    _check_sorted_key(sorted_key)
    if json:
        return snapshot()
    rows = []
    for tag, e in _entries.items():
        total = e["total"]
        idle = e.get("idle_s", 0.0)
        # device utilization under this tag between first and last
        # dispatch: busy time over busy+observed idle gaps. Only
        # meaningful where completion times were observed (the serving
        # in-flight window) — tags with no idle observations, every
        # executor's among them, render "-".
        util = (100.0 * total / (total + idle)
                if (total + idle) > 0 and e.get("gaps", 0) else None)
        rows.append((tag, e["calls"], total, e["max"],
                     0.0 if e["min"] == float("inf") else e["min"],
                     total / max(e["runs"], 1),  # mean over EXEC calls
                     e["compiles"], e["compile_s"],
                     e.get("aot_hits", 0), e.get("saved_s", 0.0),
                     idle, util))
    keyidx = {"calls": 1, "total": 2, "max": 3, "min": 4, "ave": 5}
    if sorted_key is not None:
        rows.sort(key=lambda r: r[keyidx[sorted_key]], reverse=True)
    lines = ["%-40s %8s %10s %10s %10s %10s %9s %10s %7s %9s %8s %6s" %
             ("Entry", "Calls", "Total(s)", "Max(s)", "Min(s)", "Ave(s)",
              "Compiles", "Compile(s)", "AOTHit", "Saved(s)", "Idle(s)",
              "Util%")]
    for (tag, calls, total, mx, mn, ave, ncomp, comp, ahit,
         saved, idle, util) in rows:
        lines.append("%-40s %8d %10.4f %10.4f %10.4f %10.4f %9d %10.4f "
                     "%7d %9.4f %8s %6s"
                     % (tag[:40], calls, total, mx, mn, ave, ncomp, comp,
                        ahit, saved,
                        "-" if util is None else "%.4f" % idle,
                        "-" if util is None else "%.1f" % util))
    if rows:
        cs = cache_stats()
        lines.append(
            "compile cache: %d compiles, %d AOT hits, %d warm calls, "
            "%.4fs compile time saved"
            % (cs["compiles"], cs["aot_hits"], cs["warm_calls"],
               cs["saved_s"]))
        ss = sync_stats()
        if ss["total"]:
            lines.append(
                "host syncs: %d total (%d on a dispatch path): %s"
                % (ss["total"], ss["on_dispatch_path"],
                   ", ".join("%s=%d" % kv
                             for kv in sorted(ss["by_tag"].items()))))
        lines.extend(_embedding_lines())
        lines.extend(_softmax_xent_lines())
        lines.extend(_recompute_lines())
        lines.extend(_kernel_trace_lines())
        lines.append(
            "device time by fluid op: python -m paddle_tpu.profiler <trace "
            "dir> [--by type|instance|scope] on a kept trace (scope: the "
            "whole path of scopes under a fluid op); with no trace file, "
            "profiler.device_seconds_by_op({instruction: seconds}) over the "
            "compiled steps' own names (profiler.step_op_names)")
        lines.extend(_restart_tables())
    return "\n".join(lines)


def _embedding_lines():
    """One line a kind of lookup_table lowered: its rows, its table and who
    builds the table's dense gradient, XLA's scatter or the kernel
    (`ptpu_embedding_layers_total`)."""
    from .observability.registry import REGISTRY
    lines = []
    for key, n in REGISTRY.counter(
            "ptpu_embedding_layers_total").samples():
        k = dict(key)
        lines.append("embedding: %d x %s rows of [%s, %s], gradient by %s"
                     % (n, k["rows"], k["vocab"], k["width"], k["grad"]))
    return lines


def _softmax_xent_lines():
    """One line a kind of softmax_with_cross_entropy lowered: who computes
    the loss, the dtype its rule read the logits in and whether anything
    reads the dense Softmax (`ptpu_softmax_xent_layers_total`)."""
    from .observability.registry import REGISTRY
    lines = []
    for key, n in REGISTRY.counter(
            "ptpu_softmax_xent_layers_total").samples():
        k = dict(key)
        lines.append("softmax_xent: %d x loss by %s on %s logits, Softmax %s"
                     % (n, k["path"], k["logits"], k["softmax"]))
    return lines


def _recompute_lines():
    """What a program whose loop recomputes its body (`StaticRNN(steps=,
    recompute=True)`) runs twice and what it keeps instead: forward ops a
    step and those the loop's backward scan replays
    (`ptpu_remat_ops_total`; an op whose value a loop keeps is not counted
    as replayed), then one line a recomputing loop: the values it keeps by
    fluid op and rule (`ptpu_remat_kept_values_total`) and their bytes
    (`ptpu_remat_kept_bytes`)."""
    from .observability.registry import REGISTRY
    ops = {"forward": 0.0, "replayed": 0.0}
    for key, n in REGISTRY.counter("ptpu_remat_ops_total").samples():
        ops[dict(key)["kind"]] += n
    if not ops["forward"]:
        return []
    lines = ["recompute: %d forward ops lowered, %d replayed in the backward "
             "pass (ptpu_remat_ops_total; a kept op is not replayed)"
             % (ops["forward"], ops["replayed"])]
    kept = {}
    for key, n in REGISTRY.counter(
            "ptpu_remat_kept_values_total").samples():
        k = dict(key)
        kept.setdefault(k["loop"], []).append(
            "%d x %s of %s" % (n, k["rule"], k["op"]))
    for key, size in REGISTRY.gauge("ptpu_remat_kept_bytes").samples():
        loop = dict(key)["loop"]
        lines.append(
            "recompute: loop %s keeps %s (ptpu_remat_kept_values_total), "
            "%.1f MiB (ptpu_remat_kept_bytes)"
            % (loop, ", ".join(kept.get(loop, [])) or "nothing",
               size / 2.0 ** 20))
    return lines


def _kernel_trace_lines():
    """One line: how often jax ran each kernel entry's Python body
    (`ptpu_kernel_body_traces_total`, ops/pallas_import.py): once a shape
    and a set of static arguments, where the layers' counters above say how
    many call sites a step had."""
    from .observability.registry import REGISTRY
    traces = ["%s %d" % (dict(key)["kernel"], n)
              for key, n in REGISTRY.counter(
                  "ptpu_kernel_body_traces_total").samples()]
    if not traces:
        return []
    return ["kernel bodies traced (ptpu_kernel_body_traces_total; once a "
            "shape, not once a call site): " + ", ".join(traces)]


def _seconds_table(title, family, label, limit=10):
    """One "<... by op type>  Seconds  %" block of the report: a registry
    counter's seconds summed by `label` (a tuple of labels reads "a/b"),
    most seconds first, the rows past `limit` as one."""
    from .observability.registry import REGISTRY
    names = (label,) if isinstance(label, str) else label
    by = {}
    for key, v in REGISTRY.counter(family).samples():
        row = "/".join(dict(key)[n] for n in names)
        by[row] = by.get(row, 0.0) + v
    rows = sorted(by.items(), key=lambda r: -r[1])
    if not rows:
        return []
    total = sum(v for _, v in rows) or 1.0
    lines = ["%-40s %12s %7s" % (title, "Seconds", "%")]
    lines += ["%-40s %12.4f %7.2f" % (row[:40], v, 100.0 * v / total)
              for row, v in rows[:limit]]
    if len(rows) > limit:
        rest = sum(v for _, v in rows[limit:])
        lines.append("%-40s %12.4f %7.2f" % (
            "(%d more op types)" % (len(rows) - limit), rest,
            100.0 * rest / total))
    return lines


def _restart_tables():
    """The report's last blocks, one reader for both halves of a restart:
    "Lowering(s) by op type", what the trace phase of a first run is made
    of (`ptpu_lowering_seconds_total`), then what building the Programs
    was made of: "Build(s) by phase" (`ptpu_build_seconds_total`; a phase
    holds its children, so the column is no sum: % is of `program`) and
    "Shape inference(s) by op type" (`ptpu_infer_shape_seconds_total`, a
    custom `infer` as `<op>/custom`)."""
    lines = _seconds_table("Lowering(s) by op type",
                           "ptpu_lowering_seconds_total", "op")
    lines += _build_phase_lines()
    lines += _seconds_table("Shape inference(s) by op type",
                            "ptpu_infer_shape_seconds_total", ("op", "how"))
    return lines


_BUILD_PHASES = ("program", "minimize", "append_backward", "clip",
                 "regularize", "optimize_pass")


def _build_phase_lines():
    from .observability.registry import REGISTRY
    seconds = {dict(k)["phase"]: v for k, v in REGISTRY.counter(
        "ptpu_build_seconds_total").samples()}
    ops = {dict(k)["phase"]: v for k, v in REGISTRY.counter(
        "ptpu_build_ops_total").samples()}
    if not seconds:
        return []
    whole = seconds.get("program") or max(seconds.values()) or 1.0
    lines = ["%-40s %12s %7s %9s" % ("Build(s) by phase", "Seconds", "%",
                                     "Ops")]
    lines += ["%-40s %12.4f %7.2f %9d" % (
        ("" if ph in ("program", "minimize") else "  ") + ph, seconds[ph],
        100.0 * seconds[ph] / whole, ops.get(ph, 0))
        for ph in _BUILD_PHASES if ph in seconds]
    return lines


# --- the device's per-op table ---------------------------------------------
_OPS_LINE = "XLA Ops"
_DEVICE_PLANE = "/device:TPU:"
_MOSAIC = 'custom_call_target="tpu_custom_call"'
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute", "collective-broadcast")
_device_ops = None  # the newest device_op_table(), for profile_report


def find_xplane(trace_dir):
    """The newest .xplane.pb under a jax.profiler trace directory (or
    `trace_dir` itself, if it is the file), else None."""
    if os.path.isfile(trace_dir):
        return trace_dir
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def _self_times(events):
    """[[event, self_ns, outermost]] of one line's properly nested events
    (children nest inside `while` and `conditional` parents), in the order
    they start: an event's self time is its duration less its direct
    children's (the rule of benchmark/trace_reduce.py, which the program
    may not import); `outermost` is the index in the list of the outermost
    event it lies in, its own where it lies in none."""
    timed = sorted(((float(e.start_ns), float(e.start_ns + e.duration_ns), e)
                    for e in events if e.duration_ns > 0),
                   key=lambda t: (t[0], -t[1]))
    out, stack = [], []         # stack of (index into out, end)
    for start, end, e in timed:
        while stack and stack[-1][1] <= start:
            stack.pop()
        if stack:
            out[stack[-1][0]][1] -= min(end, stack[-1][1]) - start
        out.append([e, end - start, stack[0][0] if stack else len(out)])
        stack.append((len(out) - 1, end))
    return out


def _varint(buf, i):
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message on the wire: a varint
    as an int, a length-delimited field as a slice of `buf`."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError("wire type %d: not an .xplane.pb" % wire)
        yield key >> 3, value


def read_op_names(space):
    """{HLO instruction text: HLO metadata op_name} of the device operations
    of one .xplane.pb, given as its bytes. The TPU runtime writes an operation's op_name (the
    path of named scopes it was lowered under) as the stat `tf_op` of the
    event's METADATA, which jax.profiler.ProfileData does not hand out (it
    gives an event's own stats: offsets and durations). So the few fields
    that lead there are read off the wire: XSpace.planes=1; XPlane.name=2,
    .event_metadata=4 and .stat_metadata=5 (maps: key=1, value=2);
    XEventMetadata.name=2, .stats=5; XStatMetadata.name=2;
    XStat.metadata_id=1, .str_value=5. Operations XLA adds on its own
    (prefetch copies, `slice-done`) carry no op_name and are left out."""
    op_names = {}
    for field, plane in _fields(memoryview(space)):
        if field != 1:
            continue
        name, event_meta, stat_names = "", [], {}
        for k, v in _fields(plane):
            if k == 2:
                name = bytes(v).decode()
            elif k == 4:
                event_meta.append(dict(_fields(v)).get(2, b""))
            elif k == 5:
                meta = dict(_fields(dict(_fields(v)).get(2, b"")))
                stat_names[meta.get(1)] = bytes(meta.get(2, b""))
        if not name.startswith(_DEVICE_PLANE):
            continue
        for meta in event_meta:
            text = op_name = None
            for k, v in _fields(meta):
                if k == 2:
                    text = bytes(v).decode()
                elif k == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) == b"tf_op" and 5 in stat:
                        op_name = bytes(stat[5]).decode()
            if text and op_name:
                op_names[text] = op_name
    return op_names


def device_op_table(planes, op_names, by="type"):
    """The per-op Event table of a device trace.

    `planes`: the planes of a jax.profiler.ProfileData (anything with
    `name` and `lines`; a line has `name` and `events`; an event `name`,
    `start_ns` and `duration_ns`). Only the `XLA Ops` line of the
    `/device:TPU:<n>` planes is read. `op_names`: {an event's name, the
    operation's HLO text: its HLO metadata op_name}, as `read_op_names`
    reads it from the same bytes.

    Returns {"planes": n, "busy_self_ms", "scoped_ms", "rows": [...]}: one
    row a fluid op type (`by="type"`), a fluid op instance
    (`by="instance"`: type/first output variable) or a whole path of scopes
    (`by="scope"`: `scope_path`), the named Pallas kernels
    and the collectives of a fluid op in rows of their own (`kernel`: the
    kernel's name, or `all-reduce` and the like), and every operation that
    carries no fluid scope under its own instruction name (`scoped` False),
    never dropped: the rows' `total_ms` add up to `busy_self_ms`, the self
    time of all device operations, averaged over the device planes. A row:
    {"name", "kernel", "pass" (bwd: a `<type>_grad` op; fwd: any other
    fluid op, the optimizer's among them; -: no scope), "scoped", "events",
    "total_ms", "max_ms", "min_ms", "ave_ms", "share" (% of busy_self_ms)}.
    """
    _check_by(by)
    device_planes = [p for p in planes if p.name.startswith(_DEVICE_PLANE)]
    rows, keys = {}, {}
    n = 0
    for plane in device_planes:
        events = [e for ln in plane.lines if ln.name == _OPS_LINE
                  for e in ln.events]
        if not events:
            continue
        n += 1
        for e, self_ns, _ in _self_times(events):
            key = keys.get(e.name)
            if key is None:     # one look at an operation's text
                key = keys[e.name] = _row_key(
                    e.name, op_names.get(e.name, ""), by)
            _book(rows, key, self_ns / 1e6)
    return _table(rows, n)


_BY = ("type", "instance", "scope")


def _check_by(by):
    if by not in _BY:
        raise ValueError("by must be one of %s, got %r" % (list(_BY), by))


def _book(rows, key, ms):
    row = rows.setdefault(key, [0, 0.0, 0.0, float("inf")])
    row[0] += 1
    row[1] += ms
    row[2] = max(row[2], ms)
    row[3] = min(row[3], ms)


def _table(rows, n):
    """The table of `device_op_table` from {row key: [events, ms over all
    planes, max, min]} booked over `n` device planes: the one maker of both
    reductions' tables (the trace's `tf_op`, the compiled step's map)."""
    busy = sum(r[1] for r in rows.values())
    out = []
    for (name, kernel, scoped), (count, total, mx, mn) in rows.items():
        out.append({
            "name": name, "kernel": kernel, "scoped": scoped,
            "pass": "-" if not scoped else
                    "bwd" if name.split("/")[0].endswith("_grad") else "fwd",
            "events": count, "total_ms": total / max(n, 1),
            "max_ms": mx, "min_ms": mn, "ave_ms": total / count,
            "share": 100.0 * total / busy if busy else 0.0})
    out.sort(key=lambda r: -r["total_ms"])
    return {"planes": n, "busy_self_ms": busy / max(n, 1),
            "scoped_ms": sum(
                r["total_ms"] for r in out if r["scoped"] or
                r["kernel"] and not r["kernel"].startswith(_COLLECTIVES)),
            "rows": out}


_FIRST_PASS_OP = re.compile(r"pass:\d+(?:-\d+)?/op:([^/()]+)/")


def device_pass_table(planes, op_names):
    """Device self time by pass of a stack of layers that one loop op runs
    several times over the same weights (models/causal_lm.py writes the
    passes on the loop op, "pass:1-4/op:rnn_scan/..."; its grad op has them
    too).

    The loop is one `while` on the device and the operations of its body
    nest inside it, once a trip, so the trace has no name for a trip: under
    one execution of the loop the k-th run of an instruction is trip k (an
    instruction of a loop inside the body runs n times a trip: its runs are
    dealt out n to a trip). A trip of the forward loop is that pass; the
    backward loop, the grad op's, walks the passes from the last to the
    first, and holds the replay of a pass's forward where the loop
    recomputes.

    Returns {"planes", "busy_self_ms", "rows"}: a row a pass {"pass",
    "events", "fwd_ms", "bwd_ms", "total_ms", "share"}, a row under the
    loop's own label ("1-4") for what is the loop's and no trip's (the
    `while`s' own time, what XLA hoisted out of the body) and a last row
    "outside" for everything under no pass: embedding, heads, loss,
    optimizer. Milliseconds of self time a device plane, over the whole
    trace; no rows where the trace holds no operation under a pass."""
    device_planes = [p for p in planes if p.name.startswith(_DEVICE_PLANE)]
    rows, n, busy, passes = {}, 0, 0.0, 0
    for plane in device_planes:
        events = [e for ln in plane.lines if ln.name == _OPS_LINE
                  for e in ln.events]
        if not events:
            continue
        n += 1
        timed = _self_times(events)
        runs = {}       # (outermost, instruction) -> indices of its runs
        for i, (e, self_ns, top) in enumerate(timed):
            busy += self_ns
            label = parse_pass_scope(op_names.get(e.name, ""))
            if label is not None and top != i:
                runs.setdefault((top, e.name), []).append(i)
            elif label is not None:     # the `while` itself, or hoisted
                row = rows.setdefault(label, [0, 0.0, 0.0])
                row[0] += 1
                row[1] += self_ns
        for (_, name), indices in runs.items():
            op_name = op_names[name]
            first, _, last = parse_pass_scope(op_name).partition("-")
            count = int(last or first) - int(first) + 1
            passes = max(passes, count)
            backward = _FIRST_PASS_OP.search(op_name).group(1).endswith(
                "_grad")
            a_trip = max(1, len(indices) // count)
            for k, i in enumerate(indices):
                trip = min(count - 1, k // a_trip)
                row = rows.setdefault(
                    int(first) + (count - 1 - trip if backward else trip),
                    [0, 0.0, 0.0])
                row[0] += 1
                row[2 if backward else 1] += timed[i][1]
    if not rows:
        return {"planes": n, "busy_self_ms": busy / 1e6 / max(n, 1),
                "rows": []}
    out = [{"pass": str(t), "events": rows[t][0],
            "fwd_ms": rows[t][1] / 1e6 / n, "bwd_ms": rows[t][2] / 1e6 / n}
           for t in sorted(rows, key=str)]
    inside = sum(r[1] + r[2] for r in rows.values())
    out.append({"pass": "outside", "events": 0, "fwd_ms": 0.0, "bwd_ms": 0.0,
                "total_ms": (busy - inside) / 1e6 / n})
    for r in out:
        r.setdefault("total_ms", r["fwd_ms"] + r["bwd_ms"])
        r["share"] = 100.0 * r["total_ms"] * 1e6 * n / busy
    return {"planes": n, "busy_self_ms": busy / 1e6 / n, "rows": out}


def render_pass_table(table):
    """The table `device_pass_table` made, as text; nothing where the
    program ran no pass under a name."""
    if not table["rows"]:
        return ""
    lines = ["%-8s %8s %12s %13s %11s %7s" % (
        "Pass", "Events", "Forward(ms)", "Backward(ms)", "Total(ms)",
        "Busy%")]
    for r in table["rows"]:
        lines.append("%-8s %8d %12.3f %13.3f %11.3f %7.2f" % (
            r["pass"], r["events"], r["fwd_ms"], r["bwd_ms"], r["total_ms"],
            r["share"]))
    lines.append(
        "device time by pass: a trip of the loop op's `while` is a pass "
        "(the k-th run of an instruction under one run of the loop), the "
        "backward loop's trips run from the last pass to the first and hold "
        "the replayed forward; `outside`: no pass, the heads among it")
    return "\n".join(lines)


def _row_key(text, op_name, by):
    """(row name, kernel or collective name or '', carries a fluid scope)
    of one device operation from its whole HLO instruction text and its
    op_name."""
    return _op_key(text.split(" = ", 1)[0].lstrip("%"), _MOSAIC in text,
                   op_name, by)


def _op_key(instruction, mosaic, op_name, by):
    """The row key of one HLO instruction, for every table by fluid op: the
    trace's (`_row_key`) and the compiled step's (`device_seconds_by_op`).
    GSPMD gives a collective the op_name of the fluid op whose values it
    reduces, so it is set apart as a kernel is. `by="scope"` names a row by
    the whole path of scopes the instruction was lowered under, fluid ops
    by type and jax's own scopes and primitive as they are
    ("rnn_scan/while/body/mul/dot_general": a loop op's own row is a prefix
    of its body's)."""
    base, _, n = instruction.rpartition(".")
    if not n.isdigit():
        base = instruction
    kernel = base if mosaic or base.startswith(_COLLECTIVES) else ""
    scope = parse_op_scope(op_name)
    if scope is None:
        return (instruction if by == "instance" else base), kernel, False
    if by == "scope":
        return scope_path(op_name), kernel, True
    return ("/".join(scope) if by == "instance" else scope[0]), kernel, True


def _read_trace(trace_dir):
    """(planes, {HLO text: op_name}) of the newest trace under `trace_dir`;
    ([], {}) where there is none."""
    path = find_xplane(trace_dir)
    if path is None:
        return [], {}
    with open(path, "rb") as f:
        space = f.read()
    planes = jax.profiler.ProfileData.from_serialized_xspace(space).planes
    return list(planes), read_op_names(space)


def device_op_table_from(trace_dir, by="type"):
    """`device_op_table` of the newest trace under `trace_dir` (what
    `profiler(profile_path=...)` or `benchmark/run.py --keep-trace` left
    there); an empty table where there is none."""
    planes, op_names = _read_trace(trace_dir)
    return device_op_table(planes, op_names, by)


# --- the compiled step's own names: HLO instruction -> op_name --------------
# What an executor compiled, kept weakly: an entry goes when the executor
# drops the executable (its LRU, its own end).
_steps = weakref.WeakKeyDictionary()    # executable -> _Step
_SCOPE_IN_PATH = re.compile(
    r"(?:%s\d+(?:-\d+)?/)?%s([^/()]+)/[^/()]+" % (PASS_MARK, SCOPE_MARK))
_HLO_INSTRUCTION = re.compile(r"\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope_path(op_name):
    """The whole path of scopes of an HLO op_name, as `--by scope` names a
    row: the jitted function's own name off the front, every fluid op by
    its type alone (no pass, no instance: the layers of a stack add up),
    jax's scopes and the primitive as they are. Of the paths XLA joined
    with ";" for a merged instruction, the last, as `parse_op_scope`; a
    trace's `tf_op` ends in ":", the compiled text's op_name does not."""
    path = _SCOPE_IN_PATH.sub(r"\1", op_name.rpartition(";")[2].rstrip(":"))
    head, _, rest = path.partition("/")
    return rest if rest and head.startswith(("jit(", "pjit(")) else path


class _Step(object):
    __slots__ = ("label", "args", "device", "found")

    def __init__(self, label, args, device):
        self.label, self.args, self.device = label, args, device
        self.found = None       # step_op_names' entry, once asked for


def note_step(executable, label, args=None, shardings=None, device=None):
    """An executor's hook, called once a compile and never on the warm
    path: `executable` is what its cache now holds for a step (a jitted
    function, or the `jax.stages.Compiled` of an AOT branch, which needs no
    `args`), and `args` = (feeds, state_rw, state_ro, seed) the arguments
    of the call that compiled it, of which only the description is kept:
    shape, dtype, weak type and, from `shardings` (the same lists, a
    sharding or None each, None for the seed), where the call saw the
    argument committed. jax keys its trace by the mesh of an argument's
    sharding and its lowering by which arguments were committed and where,
    so a description that differs in either misses. `device`: the
    `jax.default_device` of the call. Donated arrays are fine: a deleted
    array still says its type. Nothing is lowered or read here;
    `step_op_names` does that when asked."""
    def describe(v, sharding):
        t = jax.typeof(v)
        return jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=sharding,
                                    weak_type=t.weak_type)
    if args is not None:
        args = tuple([describe(v, sh) for v, sh in zip(vals, shs)]
                     for vals, shs in zip(args[:3], shardings)) \
            + (describe(args[3], None),)
    _steps[executable] = _Step(label, args, device)


class _WouldRetrace(Exception):
    """jax missed its trace of a step and began to run the program's rules
    again."""


@contextlib.contextmanager
def _rules_may_not_run():
    """While jax is asked for a step's lowering again, a miss of its trace
    cache must not run the program's lowering rules a second time (seconds,
    and every `ptpu_*_layers_total` booked twice): `lower_block`, which the
    step's function calls before any rule, raises on this thread."""
    from .core import lowering
    real, asking = lowering.lower_block, threading.get_ident()

    def lower_block(*args, **kwargs):
        if threading.get_ident() == asking:
            raise _WouldRetrace()
        return real(*args, **kwargs)
    lowering.lower_block = lower_block
    try:
        yield
    finally:
        lowering.lower_block = real


def _compiled_text(step, executable):
    """(the compiled module's text, None) or (None, why not) for one noted
    step, without a compile: a `Compiled` has it; a jitted function is asked
    to lower the arguments it was compiled for, which jax answers from its
    caches (the jaxpr it traced, the lowering the call made, that
    lowering's loaded executable) when they are described as the call saw
    them. Where a described argument misses, a new trace stops before the
    first rule (`_rules_may_not_run`) and a new lowering (it holds no
    executable yet) is dropped and never compiled: a second executable
    would cost seconds and the chip's memory."""
    if isinstance(executable, jax.stages.Compiled):
        return executable.as_text(), None
    if step.args is None:
        return None, "no arguments were kept for it"
    try:
        with jax.default_device(step.device), _rules_may_not_run():
            lowered = executable.lower(*step.args)
    except _WouldRetrace:
        return None, ("jax traced it anew: the described arguments miss the "
                      "call's")
    # the call's own lowering holds the executable the call loaded; one made
    # just now holds none
    if getattr(getattr(lowered, "_lowering", None), "_executable",
               None) is None:
        return None, ("jax lowered it anew: the described arguments miss "
                      "the call's")
    return lowered.compile().as_text(), None


def parse_hlo_op_names(text):
    """(module name, {HLO instruction name: (op_name, is a Mosaic call)}) of
    a compiled module's text (`Compiled.as_text()`), every computation
    included: the bodies of `while` and `conditional`, whose instructions
    the trace shows as children, and the fusions' own (a fusion carries its
    root's op_name). An instruction without metadata has op_name ''."""
    module, names = "", {}
    for line in text.splitlines():
        m = _HLO_INSTRUCTION.match(line)
        if m is None:
            if not module and line.startswith("HloModule "):
                module = line.split()[1].rstrip(",")
            continue
        found = _HLO_OP_NAME.search(line, m.end())
        names[m.group(1)] = (found.group(1) if found else "",
                             _MOSAIC in line)
    return module, names


def _names_of(executable, step):
    """`step_op_names`' entry of one noted step, made once and kept."""
    if step.found is None:
        t0 = time.perf_counter()
        try:
            text, why = _compiled_text(step, executable)
        except Exception as e:  # noqa: BLE001 — a table, never a crash
            text, why = None, "%s: %s" % (type(e).__name__, e)
        if text is None:
            step.found = {"label": step.label, "left_out": why}
        else:
            module, names = parse_hlo_op_names(text)
            step.found = {"label": step.label, "module": module,
                          "op_names": names,
                          "seconds": time.perf_counter() - t0}
    return step.found


def step_op_names():
    """What each step executable the process's executors hold says of its
    own instructions, in the order they were compiled: [{"label" (exe,
    pexe), "module" (the HLO module's name), "op_names": {instruction:
    (op_name, is a Mosaic call)}, "seconds" (what reading it cost)}], and
    {"label", "left_out": why} for one that cannot say it without a
    compile. Made when asked, from `note_step`'s descriptions, and kept;
    before a step has run, []. It never compiles: see `_compiled_text`."""
    return [_names_of(executable, step)
            for executable, step in list(_steps.items())]


def device_seconds_by_op(seconds_by_instruction, by="type"):
    """`device_op_table`'s table from {HLO instruction name: self seconds}
    (a trace's device operations as benchmark/trace_reduce.py's `top_ops`
    names them) and the compiled step's own map, with no `.xplane.pb` at
    hand: the same row key (`_op_key`), the same maker (`_table`), so the
    two tables of one trace are one table (`events` counts instructions
    here, and max / min / ave are an instruction's). Plus "step", the entry
    of `step_op_names` it read.

    The step is the one whose instructions ARE the trace's: every name
    given has to be in ONE map (the startup program's module has a
    `fusion.1` of its own: maps are never merged). The newest step is asked
    first, so a trace of what ran last reads no older step's text. None
    where no held step has them all, or none says its names."""
    _check_by(by)
    for executable, step in reversed(list(_steps.items())):
        found = _names_of(executable, step)
        names = found.get("op_names")
        if names and all(i in names for i in seconds_by_instruction):
            break
    else:
        return None
    rows = {}
    for instruction, seconds in seconds_by_instruction.items():
        op_name, mosaic = names[instruction]
        _book(rows, _op_key(instruction, mosaic, op_name, by), 1e3 * seconds)
    return dict(_table(rows, 1), step=found)


# --- the device's idle gaps, by what the program was doing -----------------
_HOST_PLANE = "/host:"


def _innermost_segments(spans):
    """Disjoint sorted (start, end, name) covering the same points as the
    host annotations `spans`, each stretch under the name of the span open
    there that started last: the innermost, where they nest."""
    bounds = sorted({t for a, b, _ in spans for t in (a, b)})
    spans = sorted(spans)
    out, live, i = [], [], 0
    for lo, hi in zip(bounds, bounds[1:]):
        while i < len(spans) and spans[i][0] <= lo:
            live.append(spans[i])
            i += 1
        live = [sp for sp in live if sp[1] > lo]
        if live:
            name = max(live, key=lambda sp: (sp[0], -sp[1]))[2]
            if out and out[-1][2] == name and out[-1][1] == lo:
                out[-1] = (out[-1][0], hi, name)
            else:
                out.append((lo, hi, name))
    return out


def idle_gaps_by_span(planes, prefix=ANNOTATION_PREFIX, longest=5):
    """The device's idle time by host annotation. A gap is a stretch between
    the first and the last operation of a `/device:TPU:<n>` plane's `XLA
    Ops` line in which none runs; each is split over the annotations named
    `<prefix>...` on the `/host:` planes by the innermost one open at each
    instant ("none" where there is none), and named by the one with the
    largest part: the rule of benchmark/trace_reduce.py for the benchmark's
    own `bench/` annotations, with nesting.

    Returns {"planes", "idle_ms" (a device), "gaps" (a device), "named_ms"
    (idle under any annotation), "by_span": [[name, ms, gaps it leads]]
    most first, "longest": [[name, ms]]}, times averaged over the device
    planes."""
    spans = [(float(e.start_ns), float(e.start_ns + e.duration_ns), e.name)
             for p in planes if p.name.startswith(_HOST_PLANE)
             for ln in p.lines for e in ln.events
             if e.name.startswith(prefix) and e.duration_ns > 0]
    segments = _innermost_segments(spans)
    starts = [sg[0] for sg in segments]
    by_span, gaps, n = {}, [], 0
    for plane in planes:
        if not plane.name.startswith(_DEVICE_PLANE):
            continue
        busy = []
        for a, b in sorted(
                (float(e.start_ns), float(e.start_ns + e.duration_ns))
                for ln in plane.lines if ln.name == _OPS_LINE
                for e in ln.events if e.duration_ns > 0):
            if busy and a <= busy[-1][1]:
                busy[-1][1] = max(busy[-1][1], b)
            else:
                busy.append([a, b])
        if not busy:
            continue
        n += 1
        for (_, a), (b, _) in zip(busy, busy[1:]):
            shares, covered = {}, 0.0
            i = max(0, bisect.bisect_right(starts, a) - 1)
            while i < len(segments) and segments[i][0] < b:
                lo, hi, name = segments[i]
                part = min(b, hi) - max(a, lo)
                if part > 0:
                    shares[name] = shares.get(name, 0.0) + part
                    covered += part
                i += 1
            if b - a > covered:
                shares["none"] = shares.get("none", 0.0) + b - a - covered
            lead = max(shares, key=shares.get)
            gaps.append((lead, b - a))
            for name, ns in shares.items():
                row = by_span.setdefault(name, [0.0, 0])
                row[0] += ns
            by_span[lead][1] += 1
    gaps.sort(key=lambda g: -g[1])
    per = 1e6 * max(n, 1)       # ns over all planes -> ms a device
    return {"planes": n,
            "idle_ms": sum(ns for _, ns in gaps) / per,
            "gaps": len(gaps) / max(n, 1),
            "named_ms": sum(v[0] for k, v in by_span.items()
                            if k != "none") / per,
            "by_span": [[k, v[0] / per, v[1]] for k, v in sorted(
                by_span.items(), key=lambda kv: -kv[1][0])],
            "longest": [[k, ns / 1e6] for k, ns in gaps[:longest]]}


def render_idle_gaps(table):
    """The block `idle_gaps_by_span` made, as text."""
    if not table["by_span"]:
        return ("idle gaps by program span: the trace holds no gap between "
                "device operations (off the chip there is no device plane)")
    idle = table["idle_ms"]
    lines = ["%-44s %11s %7s %10s" % (
        "Idle gaps by program span (innermost)", "Idle(ms)", "Idle%",
        "Gaps led")]
    for name, ms, led in table["by_span"]:
        lines.append("%-44s %11.3f %7.2f %10d" % (
            name[:44], ms, 100.0 * ms / idle if idle else 0.0, led))
    lines.append(
        "idle gaps: %.3f ms a device in %d gap(s) over %d device plane(s); "
        "%.2f%% under a program span; the longest: %s"
        % (idle, table["gaps"], table["planes"],
           100.0 * table["named_ms"] / idle if idle else 0.0,
           ", ".join("%s %.3f ms" % (k, ms) for k, ms in table["longest"])))
    return "\n".join(lines)


_DEVICE_SORT = {"calls": "events", "total": "total_ms", "max": "max_ms",
                "min": "min_ms", "ave": "ave_ms"}


def render_device_ops(table, sorted_key=None, limit=None):
    """The table `device_op_table` made, as text: most device time first,
    or by `sorted_key` as the reference's Event table was."""
    _check_sorted_key(sorted_key)
    if not table["rows"]:
        return ("device ops: the trace holds no TPU device plane (off the "
                "chip there is no device clock): nothing to list")
    rows = sorted(table["rows"],
                  key=lambda r: -r[_DEVICE_SORT[sorted_key or "total"]])
    busy = table["busy_self_ms"]
    # a row by scope is a path: as wide as the longest, within reason
    width = max(44, min(100, max(len(r["name"]) for r in rows)))
    name = "%%-%ds" % width
    lines = [(name + " %-22s %4s %8s %11s %9s %9s %9s %7s") % (
        "Device op (fluid type, else instruction)", "Kernel/collective",
        "Pass",
        "Events", "Total(ms)", "Max(ms)", "Min(ms)", "Ave(ms)", "Busy%")]
    for r in rows[:limit]:
        lines.append((name + " %-22s %4s %8d %11.3f %9.4f %9.4f %9.4f %7.2f")
                     % (r["name"][:width], r["kernel"][:22] or "-", r["pass"],
                        r["events"], r["total_ms"], r["max_ms"], r["min_ms"],
                        r["ave_ms"], r["share"]))
    if limit is not None and len(rows) > limit:
        rest = rows[limit:]
        lines.append((name + " %-22s %4s %8d %11.3f %9s %9s %9s %7.2f") % (
            "(%d more rows)" % len(rest), "", "",
            sum(r["events"] for r in rest),
            sum(r["total_ms"] for r in rest), "", "", "",
            sum(r["share"] for r in rest)))
    lines.append(
        "device ops: %.3f ms of self time a device over %d device plane(s); "
        "%.2f%% under a fluid op or a named kernel (fwd %.3f ms, bwd %.3f "
        "ms); a fusion counts under its root instruction's scope"
        % (busy, table["planes"],
           100.0 * table["scoped_ms"] / busy if busy else 0.0,
           sum(r["total_ms"] for r in rows if r["pass"] == "fwd"),
           sum(r["total_ms"] for r in rows if r["pass"] == "bwd")))
    return "\n".join(lines)


def stop_profiler(sorted_key=None, profile_path="/tmp/profile"):
    global _trace_dir, _active, _device_ops
    _active = False
    traced = False
    if _trace_dir is not None:
        try:
            jax.profiler.stop_trace()
            traced = True
        except Exception:
            pass
        _trace_dir = None
    _span[1] = time.time()
    if _span[0] is not None:
        print("[paddle_tpu.profiler] profiled %.3fs; XLA trace at %s"
              % (_span[1] - _span[0], profile_path))
    if _entries:
        print(profile_report(sorted_key))
    if traced:
        planes, op_names = _read_trace(profile_path)
        _device_ops = device_op_table(planes, op_names)
        print(render_device_ops(_device_ops, sorted_key))
        by_pass = render_pass_table(device_pass_table(planes, op_names))
        if by_pass:
            print(by_pass)


def reset_profiler():
    global _syncs_on_dispatch, _device_ops
    _entries.clear()
    _device_ops = None
    with _sync_lock:
        _syncs.clear()
        _syncs_on_dispatch = 0
    _span[0] = _span[1] = None


@contextlib.contextmanager
def cuda_profiler(*args, **kwargs):
    """Reference API kept for script compatibility; profiles the TPU."""
    with profiler():
        yield


def main(argv=None):
    """python -m paddle_tpu.profiler <trace dir>: the device's per-op table
    of a kept trace (--by type, instance or scope), its time by pass where a
    loop op ran a stack of layers several times, then its idle gaps by
    program span."""
    import argparse
    import json
    ap = argparse.ArgumentParser(
        prog="python -m paddle_tpu.profiler", description=main.__doc__)
    ap.add_argument("trace", help="a jax.profiler trace directory (what "
                    "profiler(profile_path=...) or benchmark/run.py "
                    "--keep-trace left), or an .xplane.pb file")
    ap.add_argument("--sorted-key", choices=_SORT_KEYS, default=None)
    ap.add_argument("--by", choices=_BY, default="type",
                    help="a row a fluid op type, a fluid op instance "
                    "(type/[role.]first output), or a whole path of scopes "
                    "(fluid ops by type, jax's scopes and the primitive "
                    "below them: what a layer's time is made of)")
    ap.add_argument("--limit", type=int, default=None,
                    help="print the first LIMIT rows and sum the rest")
    ap.add_argument("--json", action="store_true",
                    help="print the table as one JSON object")
    args = ap.parse_args(argv)
    if find_xplane(args.trace) is None:
        ap.error("no .xplane.pb under %s" % args.trace)
    planes, op_names = _read_trace(args.trace)
    table = device_op_table(planes, op_names, args.by)
    by_pass = device_pass_table(planes, op_names)
    gaps = idle_gaps_by_span(planes)
    if args.json:
        print(json.dumps(dict(table, by_pass=by_pass["rows"],
                              idle_gaps=gaps)))
    else:
        print(render_device_ops(table, args.sorted_key, args.limit))
        if by_pass["rows"]:
            print(render_pass_table(by_pass))
        print(render_idle_gaps(gaps))


if __name__ == "__main__":
    main()
