"""TuningStore: the versioned on-disk record of winning execution
configs.

One entry per (model signature, device key): a JSON file named by the
sha256 of that pair, carrying the knob dict the autotuner selected, the
score it measured, and enough provenance (jax version, device kind,
knob space searched, recorded_at) to audit or invalidate it. Writes are
atomic (tmp + fsync + os.replace — the checkpoint discipline, minus the
hash tree: a torn config JSON simply fails to parse and reads as "no
tuned config", which falls back to defaults, the safe direction).

The *model signature* is the program content hash
(core/compile_cache.program_content_hash) prefixed "prog:", or any
caller-chosen string ("bench:transformer") — the store does not
interpret it beyond equality. The *device key* is "platform/device_kind"
so a config tuned on one chip generation never silently applies to
another.

Store root: the ``root`` argument, else FLAGS_tuning_store_dir; with
neither there is no store, so only files the operator named shape what
is compiled. Format bumps of STORE_VERSION
invalidate every older entry (read returns None), exactly like the AOT
cache's format_version — stale tuned configs are never applied.
"""
import hashlib
import json
import os
import time

STORE_VERSION = 1
ENTRY_SUFFIX = ".tuned.json"

# knobs a TunedConfig may carry; anything else is rejected at put() so a
# typo'd knob name fails the tuning run. Two application classes — the
# rest of each entry's comment says which:
#   AUTO: picked up by apply_tuned (Executor.run / InferenceEngine)
#   OPERATOR: recorded for the deploy config, applied by setting the
#   named flag / call argument yourself (process-wide env flags cannot
#   be applied safely per-dispatch)
KNOWN_KNOBS = frozenset({
    "steps",               # AUTO: multistep K (Executor.run steps=)
    "fetch_reduce",        # AUTO: multistep fetch collapse policy
    "multistep_unroll",    # AUTO: None auto / False scan / True unroll
    "remat_segment_len",   # OPERATOR: set FLAGS_remat_segment_len
    "guard_granular",      # OPERATOR: install_numeric_guards(granular=)
    "batch_buckets",       # AUTO: serving lattice (InferenceEngine)
    "seq_buckets",         # AUTO
    "max_batch_size",      # AUTO
    "max_queue_delay_ms",  # AUTO
    # kernel-layer knobs (PR 13): recorded under "kernel:<op>/b<bucket>"
    # signatures by tuning.tune_kernels and read AT TRACE TIME by
    # ops.kernel_config.tiles_for — AUTO in the strongest sense (no
    # apply_tuned needed; trace_env_key carries the store digest so
    # compiled artifacts re-key when an entry changes)
    "block_q",             # AUTO: flash attention q-tile rows
    "block_k",             # AUTO: flash attention k-tile rows
    "block_n",             # AUTO: row-block of xent/ln/seq kernels
    "block_b",             # AUTO: batch-block of the fused LSTM kernel
    "flash_min_seq",       # AUTO: flash-vs-dense crossover (per device,
                           # signature kernel_config.CROSSOVER_SIGNATURE)
})


def resolve_store_dir(root=None):
    return root or os.environ.get("FLAGS_tuning_store_dir") or None


def device_key(device):
    """'platform/device_kind' for a jax Device (or a Place's device)."""
    return "%s/%s" % (getattr(device, "platform", str(device)),
                      getattr(device, "device_kind", ""))


def program_signature(program):
    """The content-addressed signature for a Program: stable across
    processes for byte-identical model builds (same property the AOT
    cache keys on). None when the program can't serialize."""
    from ..core.compile_cache import program_content_hash
    h = program_content_hash(program)
    return None if h is None else "prog:" + h


class TuningStore(object):
    def __init__(self, root=None):
        self.root = resolve_store_dir(root)

    def _entry_path(self, signature, dev_key):
        blob = json.dumps([signature, dev_key]).encode("utf-8")
        return os.path.join(
            self.root, hashlib.sha256(blob).hexdigest() + ENTRY_SUFFIX)

    def put(self, signature, dev_key, knobs, score=None, score_unit=None,
            searched=None, meta=None):
        """Record the winning `knobs` dict for (signature, dev_key).
        Returns the entry path. Unknown knob names raise (see
        KNOWN_KNOBS)."""
        if self.root is None:
            raise ValueError("no tuning store: set "
                             "FLAGS_tuning_store_dir or pass root=")
        bad = sorted(set(knobs) - KNOWN_KNOBS)
        if bad:
            raise ValueError("unknown tuning knob(s) %r; known: %s"
                             % (bad, sorted(KNOWN_KNOBS)))
        import jax
        record = {
            "store_version": STORE_VERSION,
            "signature": signature,
            "device_key": dev_key,
            "knobs": dict(knobs),
            "score": score,
            "score_unit": score_unit,
            "searched": searched,   # candidate list / space description
            "jax_version": jax.__version__,
            "recorded_at": time.time(),
        }
        if meta:
            record["meta"] = dict(meta)
        os.makedirs(self.root, exist_ok=True)
        path = self._entry_path(signature, dev_key)
        from ..core.utils import atomic_write_json
        atomic_write_json(path, record, fsync=True, indent=1,
                          sort_keys=True)
        return path

    def get(self, signature, dev_key):
        """The recorded entry dict, or None (missing / unreadable /
        older store version / signature mismatch — all read as
        'untuned', the safe fallback)."""
        if self.root is None or signature is None:
            return None
        path = self._entry_path(signature, dev_key)
        try:
            with open(path, "rb") as f:
                record = json.loads(f.read().decode("utf-8"))
        except (OSError, ValueError):
            return None
        if record.get("store_version") != STORE_VERSION:
            return None
        if record.get("signature") != signature or \
                record.get("device_key") != dev_key:
            return None  # hash collision or hand-edited file
        if not isinstance(record.get("knobs"), dict):
            return None
        return record

    def entries(self):
        """Every readable entry in the store (for ptpu_tune list)."""
        if self.root is None or not os.path.isdir(self.root):
            return []
        out = []
        for name in sorted(os.listdir(self.root)):
            if not name.endswith(ENTRY_SUFFIX):
                continue
            try:
                with open(os.path.join(self.root, name), "rb") as f:
                    record = json.loads(f.read().decode("utf-8"))
            except (OSError, ValueError):
                continue
            record["_file"] = name
            out.append(record)
        return out
