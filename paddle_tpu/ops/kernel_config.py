"""One owner of the kernel-layer dispatch configuration.

Every pallas fast path used to read its own env flag and run at
hard-coded block sizes (`block_q=128` literals in nn_ops, `block_n=8`
in pallas_kernels) with a single measured-once crossover
(FLAGS_flash_min_seq).  This module centralizes all three surfaces:

* **Gating** — `pallas_explicit()` / `pallas_on(op)` parse
  PADDLE_TPU_PALLAS once, in one place.  Accepted forms:
    - unset/""          : per-op default (DEFAULT_ON when the program
                          dispatches to a TPU, off on CPU)
    - "0"/"false"       : every pallas path off
    - "1"/"true"        : every pallas path on (interpret mode on CPU)
    - "attn,xent"       : allowlist — exactly the named ops on, the
                          rest off.  Unknown names raise LOUDLY (the
                          FLAGS_conv_layout discipline: a typo must not
                          silently run the other configuration).
  Op names: attn, xent, ln, lstm, seq (KERNEL_OPS).  Exception: for
  'attn' the flag is an opt-OUT only — fused_attention's positive
  dispatch is always the flash_min_seq() crossover (enabling 'attn'
  does not force flash below the crossover; pin FLAGS_flash_min_seq=0
  for that, as the kernel-coverage tests do).

* **Default tiles** — DEFAULT_TILES is the one shared table the
  per-shape candidate grids are built from; the old literals live here
  and ONLY here.

* **Tuned tiles** — `tiles_for(op, dim)` consults the TuningStore for
  a per-(op, shape-bucket, device_kind) entry recorded by
  `tuning.tune_kernels(...)` and overlays it on the defaults.  Lookups
  happen at TRACE time (inside the op lowering), so a store entry
  changes the traced computation: `kernel_env_key()` — a digest of
  every kernel:* store entry in effect — joins
  `core.lowering.trace_env_key()`, which both executors' jit caches and
  the AOT compile cache key on.  Writing a tuned entry therefore
  re-keys the compiled artifacts instead of silently serving the old
  tiles (regression-tested in test_kernel_tuning.py).

* **Crossover** — `flash_min_seq()` resolves the flash-vs-dense
  attention dispatch point: FLAGS_flash_min_seq when set (0 forces
  flash always), else a tuned `flash_min_seq` knob recorded under the
  CROSSOVER_SIGNATURE store entry for this device, else the measured
  v5e default (1024).
"""
import hashlib
import os

import jax

__all__ = [
    "KERNEL_OPS", "DEFAULT_TILES", "DEFAULT_FLASH_MIN_SEQ",
    "DEFAULT_ON", "CROSSOVER_SIGNATURE", "dispatch_device",
    "dispatch_platform",
    "pallas_explicit", "pallas_on",
    "flash_min_seq", "flash_at", "shape_bucket", "kernel_signature",
    "tiles_for", "kernel_env_key", "local_device_key",
]

# the one shared default table — the pre-tuning literals.  Keys are the
# knob names the TuningStore accepts (store.KNOWN_KNOBS); values are
# what every dispatch uses when no tuned entry exists for its
# (op, shape-bucket, device_kind).  block_b=0 means "the whole batch in
# one block" (the fused LSTM kernel's pre-knob behavior).  "attn" is what a
# sweep of block_q in {128, 256, 512, 1024} x block_k in {128, 256, 512,
# 1024} on the v5e chose for bf16 inputs at the shapes the benchmark's
# cells run ([64, 2048, 64] unmasked and causal, [64, 4096, 128] causal;
# PERF.md section 6, PR 27): at 128 x 128 the forward kernel takes 2.9-4.1
# times as long, dK/dV 2.2-2.6 and dQ 2.5-3.2 times, and the best pair is
# the same at D=64 and D=128, so the default stays one table entry and no
# function of the shape.
DEFAULT_TILES = {
    "attn": {"block_q": 512, "block_k": 512},
    "xent": {"block_n": 8},
    "ln": {"block_n": 8},
    "lstm": {"block_b": 0},
    "seq": {"block_n": 8},
}
KERNEL_OPS = frozenset(DEFAULT_TILES)
# default-on dispatch on a TPU, per op: the table a kernel leaves when
# Mosaic refuses it on the chip (chip_smoke.py phase C compiles every
# family and compares it with its XLA path), so the op takes the XLA
# path there by table rather than failing at first use
DEFAULT_ON = {"attn": True, "xent": True, "ln": True, "lstm": True,
              "seq": True}
DEFAULT_FLASH_MIN_SEQ = 1024
# store signature for the per-device flash-vs-dense crossover knob
# (shape-independent: it IS the shape rule)
CROSSOVER_SIGNATURE = "kernel:flash_crossover"


def pallas_explicit(op):
    """The explicit PADDLE_TPU_PALLAS setting for `op`: True / False,
    or None when the flag is unset (callers apply their own default).
    Single owner of the flag parse."""
    flag = os.environ.get("PADDLE_TPU_PALLAS", "")
    if flag == "":
        return None
    if flag in ("0", "false", "False"):
        return False
    if flag in ("1", "true", "True"):
        return True
    allow = set(p.strip() for p in flag.split(",") if p.strip())
    bad = sorted(allow - KERNEL_OPS)
    if bad:
        raise ValueError(
            "PADDLE_TPU_PALLAS=%r: unknown op name(s) %r; expected 0, 1 "
            "or a comma list of %s (a typo here would silently run the "
            "wrong kernel path)" % (flag, bad, sorted(KERNEL_OPS)))
    return op in allow


def dispatch_device():
    """The device the computation being traced will run on. Both
    executors trace and dispatch inside `jax.default_device(<their
    device>)`, so that pin — not the process default backend, which is
    'tpu' on a chip host even while an Executor(CPUPlace()) runs there —
    is the answer. Outside any pin (a bare kernel call in a test or
    tool) it is the default backend's first device."""
    dev = jax.config.jax_default_device
    if dev is None or isinstance(dev, str):
        dev = jax.devices(dev)[0]
    return dev


def dispatch_platform():
    """Decides between Mosaic and the interpreter, and whether an op's
    default-on kernel applies."""
    return dispatch_device().platform


def pallas_on(op):
    """Is the pallas fast path enabled for `op`?  Explicit flag wins;
    default is DEFAULT_ON[op] exactly when dispatching to a real TPU
    (interpret-mode kernels on CPU are a test/debug path, not a
    default).  `fused_attention` is the one exception: its default
    dispatch is the flash_min_seq() shape rule, so it consults
    pallas_explicit('attn') directly and treats None as 'apply the
    crossover'."""
    explicit = pallas_explicit(op)
    if explicit is not None:
        return explicit
    return DEFAULT_ON[op] and dispatch_platform() == "tpu"


def shape_bucket(dim):
    """Power-of-two bucket (>= 8) of an op's VMEM-pressure dimension —
    T for attention and sequence ops, the row width (vocab / feature
    dim) for xent/ln, the hidden size for the LSTM kernel.  Tuned
    entries are recorded and looked up per bucket so one sweep covers a
    band of real shapes without an entry per literal dim."""
    dim = max(8, int(dim))
    b = 8
    while b < dim:
        b *= 2
    return b


def kernel_signature(op, bucket):
    """TuningStore signature for a kernel-knob entry."""
    return "kernel:%s/b%d" % (op, int(bucket))


def local_device_key():
    """The store device key for the device the traced computation
    dispatches to (tuned tiles are per device generation)."""
    from ..tuning.store import device_key
    return device_key(dispatch_device())


def _store():
    from ..tuning.store import TuningStore
    return TuningStore()


def tiles_for(op, dim):
    """Resolved block knobs for `op` at VMEM-pressure dimension `dim`:
    DEFAULT_TILES overlaid with the tuned entry for
    (kernel:<op>/b<bucket>, device_kind), if recorded.  Called at trace
    time only — one store read per compiled shape, not per dispatch."""
    if op not in DEFAULT_TILES:
        raise KeyError("unknown kernel op %r (known: %s)"
                       % (op, sorted(DEFAULT_TILES)))
    knobs = dict(DEFAULT_TILES[op])
    st = _store()
    if st.root is not None:
        entry = st.get(kernel_signature(op, shape_bucket(dim)),
                       local_device_key())
        if entry is not None:
            for k in knobs:
                if k in entry["knobs"]:
                    knobs[k] = int(entry["knobs"][k])
    return knobs


_crossover_cache = {}  # root -> (dir_mtime_ns, resolved value)


def flash_min_seq():
    """Flash-vs-dense attention dispatch crossover.  Resolution order:
    FLAGS_flash_min_seq (explicit env pin; 0 forces flash always) ->
    tuned `flash_min_seq` knob for this device (CROSSOVER_SIGNATURE)
    -> 1024 (the round-4 v5e measurement: dense wins at 256, flash at
    2048).  Single owner of the read: the fused_attention dispatch and
    trace_env_key() both resolve through here.  The store lookup sits
    on trace_env_key()'s per-run path, so it caches on the store dir's
    mtime_ns like kernel_env_key (one os.stat per run, not a JSON
    parse)."""
    env = os.environ.get("FLAGS_flash_min_seq", "")
    if env:
        try:
            return int(env)
        except ValueError:
            return DEFAULT_FLASH_MIN_SEQ
    st = _store()
    if st.root is None or not os.path.isdir(st.root):
        return DEFAULT_FLASH_MIN_SEQ
    try:
        stamp = os.stat(st.root).st_mtime_ns
    except OSError:
        return DEFAULT_FLASH_MIN_SEQ
    cached = _crossover_cache.get(st.root)
    if cached is not None and cached[0] == stamp:
        return cached[1]
    value = DEFAULT_FLASH_MIN_SEQ
    entry = st.get(CROSSOVER_SIGNATURE, local_device_key())
    if entry is not None and "flash_min_seq" in entry["knobs"]:
        value = int(entry["knobs"]["flash_min_seq"])
    _crossover_cache[st.root] = (stamp, value)
    return value


def flash_at(q_len):
    """The one flash-vs-dense decision for fused_attention at query
    length `q_len` (the traced q.shape[1]; None when symbolic).

    Decode-shaped dispatch is STRUCTURAL, not a crossover knob:
    at q_len <= 1 (one query row per step — the decode-serving shape)
    the flash kernel's block_q tiling is wrong by construction (a
    128-row q block for a 1-row query; the kernel grid degenerates and
    the crossover knob was never measured there), so the dense path is
    taken unconditionally — EVEN when FLAGS_flash_min_seq=0 pins
    "flash always" for the coverage tests.  Above that:

      * explicit PADDLE_TPU_PALLAS opt-out (=0 or allowlist without
        'attn'), or DEFAULT_ON['attn'] off with the flag unset ->
        dense, regardless of length;
      * q_len >= flash_min_seq() -> flash;
      * otherwise dense.

    q_len=None (symbolic trace dim) keeps the historical behavior:
    not decode-shaped, crossover can't be evaluated, flash unless
    explicitly opted out."""
    if q_len is not None and q_len <= 1:
        return False
    explicit = pallas_explicit("attn")
    if explicit is False or (explicit is None and not DEFAULT_ON["attn"]):
        return False
    if q_len is None:
        return True
    return q_len >= flash_min_seq()


# ---------------------------------------------------------------------------
# trace-env keying: tuned tiles are trace-time state
# ---------------------------------------------------------------------------

_digest_cache = {}  # (root) -> (dir_mtime_ns, digest)


def kernel_env_key():
    """Digest of every kernel:* TuningStore entry in effect — joined
    into core.lowering.trace_env_key() so the jit caches AND the AOT
    compile cache re-key when a tuned tile changes.  Cached on the
    store directory's mtime_ns: steady state costs one os.stat per
    executor run; a put() (atomic os.replace into the dir) bumps the
    mtime and invalidates."""
    from ..tuning.store import resolve_store_dir
    root = resolve_store_dir()
    if not root or not os.path.isdir(root):
        return ""
    try:
        stamp = os.stat(root).st_mtime_ns
    except OSError:
        return ""
    cached = _digest_cache.get(root)
    if cached is not None and cached[0] == stamp:
        return cached[1]
    h = hashlib.sha256()
    st = _store()
    for record in st.entries():
        sig = record.get("signature", "")
        if not isinstance(sig, str) or not sig.startswith("kernel:"):
            continue
        h.update(repr((sig, record.get("device_key"),
                       sorted((record.get("knobs") or {}).items())))
                 .encode("utf-8"))
    digest = h.hexdigest()[:16]
    _digest_cache[root] = (stamp, digest)
    return digest
