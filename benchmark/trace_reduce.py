"""The one reduction from a profiler trace (.xplane.pb) to numbers.

Read with jax.profiler.ProfileData and nothing else. What it takes from a
trace:

  device planes  `/device:TPU:<n>`; on each the line `XLA Ops` (one event
                 an executed HLO operation, named by its whole HLO text;
                 children nest inside `while` and `conditional` parents on
                 the same line; an asynchronous collective shows as a short
                 `-start` and a `-done` that waits) and, from the line
                 `Async XLA Ops`, the collectives alone: one span a
                 collective's whole flight, start to done, beside the
                 operations that run meanwhile. It counts towards the
                 collectives' time and towards nothing else (the line's
                 copies and slices keep nothing busy that computes)
  host plane     `/host:CPU`, every line: the benchmark's own
                 jax.profiler.TraceAnnotation spans, `bench/...`

and what it gives, all in seconds and averaged over the device planes:

  window_s    first device operation's start to the last one's end
  busy_s      the union of the operations' intervals inside the window
  category_s  self time (an operation's time less its children's) by
              category: `pallas` (Mosaic custom calls), `collective`,
              `xla` (everything else XLA generated)
  collective_s, collective_exposed_s
              time with a collective running or in flight (the union of
              their intervals, so none counts twice), and the part of it
              during which nothing else ran on that device
  top_ops     [[name, seconds]]: most self time first, names as traced
  idle_gaps   [[annotation, seconds]]: the longest gaps between device
              operations, each named by the benchmark's annotation that was
              open on the host for most of it; then the idle seconds under
              each annotation, as `total:<annotation>`

A trace with no device plane (a CPU rehearsal) gives zeros and empty lists.
"""
import bisect
import functools
import glob
import os

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
ANNOTATION_PREFIX = "bench/"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
MOSAIC_TARGET = "tpu_custom_call"
N_GAPS = 5


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return found[-1]


def reduce_dir(trace_dir):
    return reduce_file(find_xplane(trace_dir))


def reduce_file(path):
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes)


@functools.lru_cache(maxsize=1 << 16)
def parse_op(text):
    """(short name, opcode, detail) of one device operation. The TPU trace
    names an operation by its whole HLO instruction,
        %name = <shape> opcode(<operands>), attr=..., ...
    whose operands carry other instructions' names, so only the opcode
    (behind the shape) and the attributes (behind the operands) say what it
    is. detail is the custom call's target or the fusion's kind."""
    if " = " not in text:
        return text, "", ""
    name, rest = text.split(" = ", 1)
    rest = rest.lstrip()
    if rest.startswith("("):            # a tuple shape: skip to its end
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        rest = rest[i + 1:]
    else:
        rest = rest.partition(" ")[2]
    opcode = rest.lstrip().partition("(")[0].strip()
    detail = ""
    for key in ('custom_call_target="', "kind="):
        if key in text:
            detail = text.rpartition(key)[2].split('"')[0].split(",")[0]
            break
    return name.lstrip("%"), opcode, detail.strip()


def category(text):
    """`pallas`, `collective` or `xla` for one device operation."""
    _, opcode, detail = parse_op(text)
    if any(opcode.startswith(w) for w in COLLECTIVES):
        return "collective"
    if opcode == "custom-call" and detail == MOSAIC_TARGET:
        return "pallas"
    return "xla"


def short_name(text):
    name, opcode, detail = parse_op(text)
    return " ".join(x for x in (name, opcode, detail) if x)[:120]


def _events(line):
    out = [(float(e.start_ns), float(e.start_ns + e.duration_ns), e.name)
           for e in line.events if e.duration_ns > 0]
    out.sort(key=lambda ev: (ev[0], -ev[1]))
    return out


def _self_times(events):
    """[[start, end, name, self_ns, is_leaf]] for properly nested events
    sorted by (start, -end): an event's self time is its duration less its
    direct children's."""
    out, stack = [], []         # stack of (index into out, end)
    for start, end, name in events:
        while stack and stack[-1][1] <= start:
            stack.pop()
        if stack:               # a child: take its time off the parent
            out[stack[-1][0]][3] -= min(end, stack[-1][1]) - start
            out[stack[-1][0]][4] = False
        out.append([start, end, name, end - start, True])
        stack.append((len(out) - 1, end))
    return out


def _union(intervals):
    """Disjoint sorted intervals covering the same points."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _covered(a, b, merged, starts):
    """Length of [a, b] covered by the disjoint sorted `merged`, whose
    starts are `starts`."""
    total = 0.0
    for x, y in merged[max(0, bisect.bisect_right(starts, a) - 1):]:
        if x >= b:
            break
        total += max(0.0, min(b, y) - max(a, x))
    return total


def _annotations_over(spans, starts, a, b):
    """{annotation: ns} of the idle gap [a, b] by the benchmark annotation
    open on the host ('none' where there is none). The benchmark's
    annotations follow one another and do not nest."""
    out, covered = {}, 0.0
    i = max(0, bisect.bisect_right(starts, a) - 1)
    while i < len(spans) and spans[i][0] < b:
        overlap = min(b, spans[i][1]) - max(a, spans[i][0])
        if overlap > 0:
            out[spans[i][2]] = out.get(spans[i][2], 0.0) + overlap
            covered += overlap
        i += 1
    if b - a > covered:
        out["none"] = b - a - covered
    return out


def reduce_planes(planes):
    planes = list(planes)       # ProfileData hands out a one-shot iterator
    device_planes = [p for p in planes if p.name.startswith("/device:TPU:")
                     and any(ln.name == OPS_LINE for ln in p.lines)]
    host_spans = sorted(
        (float(e.start_ns), float(e.start_ns + e.duration_ns), e.name)
        for p in planes if p.name.startswith("/host:")
        for ln in p.lines for e in ln.events
        if e.name.startswith(ANNOTATION_PREFIX))
    span_starts = [sp[0] for sp in host_spans]
    n = len(device_planes)
    summary = {"planes": [p.name for p in device_planes], "window_s": 0.0,
               "busy_s": 0.0, "category_s": {}, "collective_s": 0.0,
               "collective_exposed_s": 0.0, "top_ops": [], "idle_gaps": [],
               "n_ops": 0}
    by_op, gaps, by_annotation = {}, [], {}
    for plane in device_planes:
        events, in_flight = [], []
        for ln in plane.lines:
            if ln.name == OPS_LINE:
                events += _events(ln)
            elif ln.name == ASYNC_LINE:
                in_flight += [(ev[0], ev[1]) for ev in _events(ln)
                              if category(ev[2]) == "collective"]
        events = _self_times(sorted(events, key=lambda ev: (ev[0], -ev[1])))
        if not events:
            continue
        summary["n_ops"] += len(events)
        kinds = {}
        for e in events:
            if e[2] not in kinds:
                kinds[e[2]] = (category(e[2]), short_name(e[2]))
        busy = _union([(e[0], e[1]) for e in events])
        summary["window_s"] += (busy[-1][1] - busy[0][0]) / 1e9 / n
        summary["busy_s"] += sum(b - a for a, b in busy) / 1e9 / n
        for start, end, name, self_ns, _ in events:
            kind, op = kinds[name]
            summary["category_s"][kind] = summary["category_s"].get(
                kind, 0.0) + self_ns / 1e9 / n
            by_op[op] = by_op.get(op, 0.0) + self_ns / 1e9 / n
        # what computes: the operations with nothing nested in them (a
        # `while` spans its body, collectives and all)
        compute = _union([(e[0], e[1]) for e in events
                          if e[4] and kinds[e[2]][0] != "collective"])
        compute_starts = [c[0] for c in compute]
        for a, b in _union(in_flight + [
                (e[0], e[1]) for e in events
                if kinds[e[2]][0] == "collective"]):
            summary["collective_s"] += (b - a) / 1e9 / n
            summary["collective_exposed_s"] += (
                b - a - _covered(a, b, compute, compute_starts)) / 1e9 / n
        for (_, a), (b, _) in zip(busy, busy[1:]):
            shares = _annotations_over(host_spans, span_starts, a, b)
            gaps.append((max(shares, key=shares.get), (b - a) / 1e9))
            for name, ns in shares.items():
                by_annotation[name] = by_annotation.get(name, 0.0) \
                    + ns / 1e9 / n
    summary["top_ops"] = [[k, v] for k, v in sorted(
        by_op.items(), key=lambda kv: -kv[1])]
    gaps.sort(key=lambda g: -g[1])
    summary["idle_gaps"] = [[k, v] for k, v in gaps[:N_GAPS]] + [
        ["total:" + k, v] for k, v in sorted(
            by_annotation.items(), key=lambda kv: -kv[1])[:N_GAPS]]
    return summary

