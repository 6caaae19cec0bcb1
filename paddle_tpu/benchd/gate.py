"""Perf-regression gate: fresh lines vs last-good-hardware baselines
(ARCHITECTURE.md §28).

Correctness regressions fail CI; this module does the same for perf.
The gate compares fresh bench records against the store's `last_good()`
baseline for the same (metric, device_kind, config digest) key:

  * error placeholders are SKIPPED, never failed — a run that died
    before it measured (rc=3, value 0.0) must read as a failed run,
    not as a 100% throughput regression (the baseline rule).
  * min-of-repeats: repeated fresh runs of one config reduce to the
    least-noise representative (max for higher-is-better throughput,
    min for lower-is-better latency) before comparing — one noisy
    repeat must not fail a healthy config.
  * per-metric relative noise bands: hardware throughput jitters; the
    default band is 10%, serving/fleet qps legs (scheduler-noise-bound)
    get wider bands. A fresh value below baseline*(1-band) is a
    regression; above baseline*(1+band) is an improvement; in between
    is within-noise.
  * ONLY same-config comparisons can regress.  A fresh record whose
    exact (metric, device_kind, digest) key has no good baseline
    passes as `no-baseline` — with the nearest (metric, device_kind)
    value quoted informationally when one exists.  Gating a batch-8
    pipeline line against a batch-256 baseline would flag every new
    configuration as a regression; cross-config ratios are context,
    never verdicts.

Verdict per fresh key, exit semantics (tools/ptpu_bench.py):
0 = no regressions, 1 = at least one regression, 2 = bad invocation.
"""
from . import schema

__all__ = ["DEFAULT_NOISE_BAND", "NOISE_BANDS", "LOWER_IS_BETTER",
           "noise_band_for", "metric_direction", "run_gate"]

DEFAULT_NOISE_BAND = 0.10

# per-metric relative noise bands where the default is too tight:
# closed/open-loop serving legs ride thread schedulers and admission
# control; fleet/decode legs add autoscaler/slot-retirement timing
NOISE_BANDS = {
    "serving_throughput": 0.15,
    "serving_pool_throughput": 0.15,
    "serving_fleet_autoscale_qps": 0.20,
    "pipeline_dispatch_open_qps": 0.20,
    "decode_continuous_tokens_per_sec": 0.15,
    "ckpt_async_steps_per_sec": 0.15,
    # 0.20 (was 0.15): the PR-10 flake post-mortem — the resil leg
    # gates a guard/no-guard RATIO on a dispatch-bound smoke model,
    # where one executable relayout between bench store entries moves
    # the headline past 15% with no code change; bench.py's min-of-five
    # interleaved rounds shrinks within-run noise but cannot touch
    # across-run compile lottery
    "resil_guarded_steps_per_sec": 0.20,
    "sentinel_steps_per_sec": 0.15,
}

# metrics where a SMALLER value is better. Every current headline is
# throughput-shaped; latency-shaped units are also sniffed so a future
# p99 leg defaults sanely even if unlisted here.
LOWER_IS_BETTER = frozenset((
    "serving_p99_ms",
    "decode_inter_token_p99_ms",
))
_LOWER_UNIT_HINTS = ("ms", "seconds", "s/step")


def metric_direction(metric, unit=""):
    """+1 = higher is better (throughput), -1 = lower is better."""
    if metric in LOWER_IS_BETTER:
        return -1
    u = (unit or "").lower()
    if any(h in u for h in _LOWER_UNIT_HINTS):
        return -1
    return 1


def noise_band_for(metric, overrides=None):
    if overrides and metric in overrides:
        return float(overrides[metric])
    return NOISE_BANDS.get(metric, DEFAULT_NOISE_BAND)


def _fresh_groups(entries):
    """Group envelopes by (metric, device_kind, digest), keeping order."""
    groups = {}
    for env in entries:
        key = (env.get("metric"), env.get("device_kind"),
               env.get("digest"))
        groups.setdefault(key, []).append(env)
    return groups


def _representative(envs, direction):
    """Min-of-repeats: the least-noise value among the good repeats
    (max for throughput, min for latency)."""
    vals = [e["record"]["value"] for e in envs]
    pick = max(vals) if direction > 0 else min(vals)
    for e in envs:
        if e["record"]["value"] == pick:
            return e, len(vals)
    return envs[-1], len(vals)


def run_gate(store, fresh=None, noise_overrides=None):
    """Gate `fresh` envelopes (or, with fresh=None, the store's newest
    entry per key — the self-gating mode over whatever the store
    holds) against the store's last-good baselines.

    Returns {"verdicts": [...], "counts": {...}, "regressions": N,
    "exit_code": 0|1}.  Each verdict carries metric/device_kind/digest,
    the verdict string (regression | improvement | within-noise |
    error-skipped | no-baseline), value, baseline value+source, the
    band used, repeats folded, and a human detail line.
    """
    if fresh is None:
        newest = {}
        for env in store.entries():
            key = (env.get("metric"), env.get("device_kind"),
                   env.get("digest"))
            cur = newest.get(key)
            if cur is None or (env.get("ts", 0), env.get("seq", 0)) \
                    >= (cur.get("ts", 0), cur.get("seq", 0)):
                newest[key] = env
        fresh = list(newest.values())
    verdicts = []
    counts = {"regression": 0, "improvement": 0, "within-noise": 0,
              "error-skipped": 0, "no-baseline": 0}

    for key, envs in sorted(_fresh_groups(fresh).items(),
                            key=lambda kv: (kv[0][0] or "",
                                            kv[0][1] or "",
                                            kv[0][2] or "")):
        metric, dkind, digest = key
        good = [e for e in envs if not schema.is_error(e["record"])]
        v = {"metric": metric, "device_kind": dkind, "digest": digest}
        if not good:
            errs = [e["record"].get("error", "") for e in envs]
            v.update(verdict="error-skipped", repeats=len(envs),
                     detail="all %d fresh record(s) are error "
                            "placeholders (%s) — skipped per the "
                            "baseline rule, not a regression"
                            % (len(envs), (errs[0] or "?")[:80]))
            verdicts.append(v)
            counts["error-skipped"] += 1
            continue
        unit = good[-1]["record"].get("unit", "")
        direction = metric_direction(metric, unit)
        rep, repeats = _representative(good, direction)
        value = float(rep["record"]["value"])
        # exclude the fresh entries themselves from baseline resolution
        # (self-gating mode feeds store entries back in)
        fresh_seqs = {e.get("seq") for e in envs if "seq" in e}
        min_fresh_seq = min(fresh_seqs) if fresh_seqs else None
        base = store.last_good(metric, device_kind=dkind, digest=digest,
                               before_seq=min_fresh_seq)
        v.update(value=value, unit=unit, repeats=repeats,
                 direction=direction)
        if base is None:
            # no same-config baseline: pass.  Quote the nearest
            # same-metric value as context only — cross-config ratios
            # are never verdicts.
            near = store.last_good(metric, device_kind=dkind,
                                   before_seq=min_fresh_seq)
            ctx = ""
            if near is not None:
                ctx = " (nearest %s value for context: %.4g, " \
                      "different config — not gated)" \
                      % (metric, float(near["record"]["value"]))
            v.update(verdict="no-baseline",
                     detail="no last-good %s baseline for this %s "
                            "config — first hardware window for this "
                            "leg passes%s" % (dkind, metric, ctx))
            verdicts.append(v)
            counts["no-baseline"] += 1
            continue
        bval = float(base["record"]["value"])
        band = noise_band_for(metric, noise_overrides)
        v.update(baseline=bval, baseline_source=base.get("source"),
                 baseline_seq=base.get("seq"), band=band)
        if bval == 0.0:
            verdict = "within-noise" if value >= 0 else "regression"
            ratio = None
        else:
            ratio = value / bval
            if direction > 0:
                verdict = ("regression" if ratio < 1.0 - band else
                           "improvement" if ratio > 1.0 + band else
                           "within-noise")
            else:
                verdict = ("regression" if ratio > 1.0 + band else
                           "improvement" if ratio < 1.0 - band else
                           "within-noise")
        v.update(verdict=verdict, ratio=ratio,
                 detail="%s %s=%.4g vs last-good %.4g (%s) band "
                        "±%d%%: %s"
                        % (metric, unit, value, bval,
                           base.get("source", "?"),
                           round(band * 100), verdict))
        verdicts.append(v)
        counts[verdict] += 1

    return {"verdicts": verdicts, "counts": counts,
            "regressions": counts["regression"],
            "exit_code": 1 if counts["regression"] else 0}
