"""Per-shape kernel block autotuning (ARCHITECTURE.md §25): the
kernel_config flag/tile surface, the TuningStore round-trip for kernel
knobs, tune_kernels, and the one invariant everything hangs on — a
recorded tile entry changes the kernel's block parameters at the next
trace AND re-keys the compiled-program caches (trace_env_key carries
the store digest, so a tuned entry can never silently serve a stale
executable built at the old tiles)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu.ops import kernel_config as kc
from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.tuning import TuningStore

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


# ---------------------------------------------------------------------------
# flag surface: one owner, 0/1 + allowlist forms
# ---------------------------------------------------------------------------

def test_pallas_flag_forms(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_PALLAS", raising=False)
    assert kc.pallas_explicit("xent") is None
    for off in ("0", "false", "False"):
        monkeypatch.setenv("PADDLE_TPU_PALLAS", off)
        assert kc.pallas_explicit("xent") is False
        assert kc.pallas_on("xent") is False
    for on in ("1", "true", "True"):
        monkeypatch.setenv("PADDLE_TPU_PALLAS", on)
        assert kc.pallas_explicit("lstm") is True
        assert kc.pallas_on("lstm") is True
    # allowlist form: exactly the named ops on, the rest off
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "attn,xent")
    assert kc.pallas_on("attn") is True
    assert kc.pallas_on("xent") is True
    assert kc.pallas_on("ln") is False
    assert kc.pallas_on("lstm") is False
    assert kc.pallas_on("seq") is False


def test_pallas_flag_typo_raises(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "attn,xnet")
    with pytest.raises(ValueError, match="xnet"):
        kc.pallas_explicit("attn")


def test_dispatch_platform_is_the_pinned_device_not_the_backend(monkeypatch):
    """Both executors trace inside jax.default_device(<their device>):
    that pin, not the process default backend, decides Mosaic vs the
    interpreter — an Executor(CPUPlace()) on a TPU host must never hand
    Mosaic a CPU compile."""
    class Chip(object):
        platform = "tpu"

    cpu = jax.devices("cpu")[0]
    monkeypatch.setattr(jax, "devices", lambda *a: [Chip()])
    assert kc.dispatch_platform() == "tpu"          # no pin: the backend
    assert pk._interpret_default() is False
    with jax.default_device(cpu):
        assert kc.dispatch_platform() == "cpu"      # the pin wins
        assert pk._interpret_default() is True
        monkeypatch.delenv("PADDLE_TPU_PALLAS", raising=False)
        assert kc.pallas_on("ln") is False


def test_default_on_table_decides_on_a_tpu(monkeypatch):
    """With the flag unset, an op is on exactly when it dispatches to a
    TPU AND its DEFAULT_ON entry holds — the table a kernel leaves when
    Mosaic refuses it; the explicit flag still wins."""
    monkeypatch.delenv("PADDLE_TPU_PALLAS", raising=False)
    monkeypatch.setattr(kc, "dispatch_platform", lambda: "tpu")
    assert set(kc.DEFAULT_ON) == set(kc.KERNEL_OPS)
    assert all(kc.pallas_on(op) for op in kc.KERNEL_OPS)
    monkeypatch.setitem(kc.DEFAULT_ON, "lstm", False)
    monkeypatch.setitem(kc.DEFAULT_ON, "attn", False)
    assert kc.pallas_on("lstm") is False and kc.pallas_on("ln") is True
    assert kc.flash_at(4096) is False               # dense by table
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "lstm,attn")
    assert kc.pallas_on("lstm") is True and kc.flash_at(4096) is True


def test_no_flag_means_no_tuning_store(monkeypatch):
    """Tiles, the flash crossover and every cache key are read at trace
    time: with FLAGS_tuning_store_dir unset nothing outside the checkout
    can shape what is compiled."""
    monkeypatch.delenv("FLAGS_tuning_store_dir", raising=False)
    assert TuningStore().root is None
    assert kc.kernel_env_key() == ""
    assert kc.tiles_for("attn", 2048) == kc.DEFAULT_TILES["attn"]
    monkeypatch.delenv("FLAGS_flash_min_seq", raising=False)
    assert kc.flash_min_seq() == kc.DEFAULT_FLASH_MIN_SEQ
    with pytest.raises(ValueError, match="no tuning store"):
        TuningStore().put("kernel:attn/b2048", "cpu/cpu", {"block_q": 64})


@pytest.mark.parametrize("t", [2048, 4096], ids=["t2048_d64", "t4096_d128"])
def test_default_attn_tiles_are_the_sweeps_choice(monkeypatch, t):
    """PERF.md section 6, PR 27: 512 x 512 won the v5e sweep at both shapes
    the benchmark's cells run, D=64 and D=128 alike, so the default is one
    table entry with no dependence on the head width."""
    monkeypatch.delenv("FLAGS_tuning_store_dir", raising=False)
    assert kc.tiles_for("attn", t) == {"block_q": 512, "block_k": 512}


def test_shape_bucket():
    assert kc.shape_bucket(1) == 8
    assert kc.shape_bucket(8) == 8
    assert kc.shape_bucket(9) == 16
    assert kc.shape_bucket(128) == 128
    assert kc.shape_bucket(129) == 256
    assert kc.shape_bucket(2048) == 2048


# ---------------------------------------------------------------------------
# store round-trip for kernel knobs
# ---------------------------------------------------------------------------

def test_kernel_knobs_store_roundtrip(tmp_path):
    st = TuningStore(root=str(tmp_path))
    sig = kc.kernel_signature("attn", 256)
    st.put(sig, "cpu/", {"block_q": 64, "block_k": 256}, score=1.0,
           score_unit="units/sec")
    entry = st.get(sig, "cpu/")
    assert entry["knobs"] == {"block_q": 64, "block_k": 256}
    # typo'd knob names fail the put, not a later silent miss
    with pytest.raises(ValueError, match="blockq"):
        st.put(sig, "cpu/", {"blockq": 64})


def test_tiles_for_overlays_tuned_entry(monkeypatch, tmp_path):
    monkeypatch.setenv("FLAGS_tuning_store_dir", str(tmp_path))
    assert kc.tiles_for("attn", 100) == kc.DEFAULT_TILES["attn"]
    st = TuningStore()
    st.put(kc.kernel_signature("attn", kc.shape_bucket(100)),
           kc.local_device_key(), {"block_q": 32, "block_k": 64})
    assert kc.tiles_for("attn", 100) == {"block_q": 32, "block_k": 64}
    # other buckets stay at the defaults
    assert kc.tiles_for("attn", 1000) == kc.DEFAULT_TILES["attn"]
    # and unknown ops stay loud
    with pytest.raises(KeyError):
        kc.tiles_for("nosuch", 64)


def test_flash_min_seq_resolution(monkeypatch, tmp_path):
    monkeypatch.setenv("FLAGS_tuning_store_dir", str(tmp_path))
    monkeypatch.delenv("FLAGS_flash_min_seq", raising=False)
    assert kc.flash_min_seq() == kc.DEFAULT_FLASH_MIN_SEQ
    TuningStore().put(kc.CROSSOVER_SIGNATURE, kc.local_device_key(),
                      {"flash_min_seq": 512})
    assert kc.flash_min_seq() == 512       # tuned crossover
    monkeypatch.setenv("FLAGS_flash_min_seq", "64")
    assert kc.flash_min_seq() == 64        # explicit env pin wins


def test_flash_at_decode_shape_is_structurally_dense(monkeypatch):
    """q_len <= 1 (the decode-serving shape) takes the dense path by
    construction — not even FLAGS_flash_min_seq=0 ("flash always")
    forces the kernel there, because no valid flash q-tiling exists for
    a one-row query block."""
    monkeypatch.setenv("FLAGS_flash_min_seq", "0")
    monkeypatch.delenv("PADDLE_TPU_PALLAS", raising=False)
    assert kc.flash_at(1) is False
    assert kc.flash_at(0) is False
    # above the decode shape, min_seq=0 still means flash always
    assert kc.flash_at(2) is True
    assert kc.flash_at(4096) is True
    # explicit opt-out beats length at any shape
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "xent,ln")
    assert kc.flash_at(4096) is False
    # crossover behavior preserved above the structural rule
    monkeypatch.delenv("PADDLE_TPU_PALLAS", raising=False)
    monkeypatch.setenv("FLAGS_flash_min_seq", "256")
    assert kc.flash_at(128) is False
    assert kc.flash_at(256) is True
    # symbolic (None) keeps the historical not-decode default: flash
    assert kc.flash_at(None) is True


def test_fused_attention_decode_shape_never_calls_flash(monkeypatch):
    """End-to-end: a q_len=1 fused_attention never reaches the pallas
    kernel even under the flash-always pin, and matches the dense
    reference (same math; jit-vs-eager only differs at ulp level)."""
    monkeypatch.setenv("FLAGS_flash_min_seq", "0")
    monkeypatch.delenv("PADDLE_TPU_PALLAS", raising=False)
    called = []
    real = pk.flash_attention
    monkeypatch.setattr(pk, "flash_attention",
                        lambda *a, **k: called.append(1) or real(*a, **k))
    rng = np.random.RandomState(7)
    qn = (rng.randn(2, 1, 2, 8) * 0.5).astype("float32")
    kn = (rng.randn(2, 16, 2, 8) * 0.5).astype("float32")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        q = fluid.layers.data(name="q", shape=[1, 2, 8], dtype="float32")
        k = fluid.layers.data(name="k", shape=[16, 2, 8],
                              dtype="float32")
        out = fluid.layers.fused_attention(q, k, k)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        called.clear()
        got, = exe.run(main, feed={"q": qn, "k": kn}, fetch_list=[out])
    assert not called
    from paddle_tpu.parallel.ring_attention import attention_reference
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(attention_reference(qn, kn, kn).astype("float32")),
        rtol=2e-6, atol=2e-7)


# ---------------------------------------------------------------------------
# the re-key invariant
# ---------------------------------------------------------------------------

def test_trace_env_key_rekeys_on_kernel_entries_only(monkeypatch,
                                                     tmp_path):
    from paddle_tpu.core.lowering import trace_env_key
    monkeypatch.setenv("FLAGS_tuning_store_dir", str(tmp_path))
    key0 = trace_env_key()
    # a NON-kernel tuning entry (multistep K) must not retrace anything
    TuningStore().put("prog:deadbeef", kc.local_device_key(),
                      {"steps": 8})
    assert trace_env_key() == key0
    # a kernel tile entry must re-key
    TuningStore().put(kc.kernel_signature("ln", 64),
                      kc.local_device_key(), {"block_n": 32})
    key1 = trace_env_key()
    assert key1 != key0
    # and a crossover entry again (flash_min_seq is trace-time state)
    TuningStore().put(kc.CROSSOVER_SIGNATURE, kc.local_device_key(),
                      {"flash_min_seq": 256})
    assert trace_env_key() != key1


def test_tuned_tiles_change_dispatch_and_rekey_jit_cache(monkeypatch,
                                                         tmp_path):
    """The acceptance invariant end to end: run a fused_attention
    program (kernel forced via min_seq=0), record a tuned tile entry
    for its shape bucket, run again — the SAME program re-traces (new
    jit-cache key; the AOT cache keys on the same trace_env_key tuple)
    and the kernel is entered with the TUNED block sizes."""
    monkeypatch.setenv("FLAGS_tuning_store_dir", str(tmp_path))
    monkeypatch.setenv("FLAGS_flash_min_seq", "0")
    monkeypatch.delenv("PADDLE_TPU_PALLAS", raising=False)

    seen = []
    real = pk.flash_attention

    def spy(*args, **kwargs):
        seen.append((kwargs.get("block_q"), kwargs.get("block_k")))
        return real(*args, **kwargs)

    monkeypatch.setattr(pk, "flash_attention", spy)

    rng = np.random.RandomState(3)
    b, t, h, d = 2, 16, 2, 8
    qn = (rng.randn(b, t, h, d) * 0.5).astype("float32")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        q = fluid.layers.data(name="q", shape=[t, h, d], dtype="float32")
        out = fluid.layers.fused_attention(q, q, q)   # tiles unpinned
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        seen.clear()
        r1, = exe.run(main, feed={"q": qn}, fetch_list=[out])
        cached_after_first = len(exe._cache)
        assert seen and seen[-1] == (
            kc.DEFAULT_TILES["attn"]["block_q"],
            kc.DEFAULT_TILES["attn"]["block_k"])

        # second run, same config: cache hit, no re-trace
        seen.clear()
        exe.run(main, feed={"q": qn}, fetch_list=[out])
        assert len(exe._cache) == cached_after_first
        assert not seen

        # record tuned tiles for this bucket -> re-trace at new blocks
        TuningStore().put(kc.kernel_signature("attn", kc.shape_bucket(t)),
                          kc.local_device_key(),
                          {"block_q": 8, "block_k": 8})
        seen.clear()
        r2, = exe.run(main, feed={"q": qn}, fetch_list=[out])
        assert len(exe._cache) == cached_after_first + 1
        assert seen and seen[-1] == (8, 8)
    # tiles are a pure perf knob: results identical either way
    np.testing.assert_allclose(np.asarray(r1), np.asarray(r2),
                               rtol=2e-5, atol=2e-6)


def test_explicit_layer_tiles_pin_over_tuned(monkeypatch, tmp_path):
    """An explicit block_q/block_k on the layer wins over the store."""
    monkeypatch.setenv("FLAGS_tuning_store_dir", str(tmp_path))
    monkeypatch.setenv("FLAGS_flash_min_seq", "0")
    seen = []
    real = pk.flash_attention
    monkeypatch.setattr(
        pk, "flash_attention",
        lambda *a, **k: seen.append((k.get("block_q"), k.get("block_k")))
        or real(*a, **k))
    t = 16
    TuningStore().put(kc.kernel_signature("attn", kc.shape_bucket(t)),
                      kc.local_device_key(), {"block_q": 8, "block_k": 8})
    rng = np.random.RandomState(5)
    qn = (rng.randn(1, t, 2, 8) * 0.5).astype("float32")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        q = fluid.layers.data(name="q", shape=[t, 2, 8], dtype="float32")
        out = fluid.layers.fused_attention(q, q, q, block_q=16,
                                           block_k=16)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        seen.clear()
        exe.run(main, feed={"q": qn}, fetch_list=[out])
    assert seen and seen[-1] == (16, 16)


def test_pallas_opt_out_forces_dense_attention(monkeypatch):
    """PADDLE_TPU_PALLAS without 'attn' forces the dense path even
    under min_seq=0 (the per-op opt-out half of the allowlist)."""
    monkeypatch.setenv("FLAGS_flash_min_seq", "0")
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "xent,ln")
    called = []
    real = pk.flash_attention
    monkeypatch.setattr(pk, "flash_attention",
                        lambda *a, **k: called.append(1) or real(*a, **k))
    rng = np.random.RandomState(6)
    qn = (rng.randn(1, 12, 2, 8) * 0.5).astype("float32")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        q = fluid.layers.data(name="q", shape=[12, 2, 8], dtype="float32")
        out = fluid.layers.fused_attention(q, q, q)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        called.clear()
        got, = exe.run(main, feed={"q": qn}, fetch_list=[out])
    assert not called
    from paddle_tpu.parallel.ring_attention import attention_reference
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(attention_reference(qn, qn, qn)),
        rtol=2e-5, atol=2e-6)


# ---------------------------------------------------------------------------
# tune_kernels
# ---------------------------------------------------------------------------

def test_tune_kernels_records_and_applies(monkeypatch, tmp_path):
    from paddle_tpu import tuning
    monkeypatch.setenv("FLAGS_tuning_store_dir", str(tmp_path))
    res = tuning.tune_kernels(
        ops=("xent", "ln"),
        shapes={"xent": [dict(n=8, v=32)], "ln": [dict(n=8, d=16)]},
        repeats=1, include_crossover=False)
    assert set(res["entries"]) == {
        kc.kernel_signature("xent", 32), kc.kernel_signature("ln", 16)}
    for sig, result in res["entries"].items():
        assert result.store_path and os.path.exists(result.store_path)
        assert result.best_score > 0
    # the winner is what the dispatch now resolves
    best = res["entries"][kc.kernel_signature("xent", 32)].best
    assert kc.tiles_for("xent", 32) == best


def test_tune_kernels_crossover_records_flash_min_seq(monkeypatch,
                                                      tmp_path):
    from paddle_tpu import tuning
    monkeypatch.setenv("FLAGS_tuning_store_dir", str(tmp_path))
    monkeypatch.delenv("FLAGS_flash_min_seq", raising=False)
    res = tuning.tune_kernels(
        ops=("attn",), shapes={"attn": [dict(b=1, h=1, d=8, t=16)]},
        repeats=1, include_crossover=True)
    assert res["crossover"] is not None
    assert kc.flash_min_seq() == res["crossover"]


@pytest.mark.slow
def test_ptpu_tune_kernels_cli_smoke(tmp_path):
    """Zero-to-tuned through the CLI (the deploy path the sweep's
    tier-3 leg runs on hardware). Slow-marked: the in-process
    tune_kernels tests above cover the search/record logic; this leg
    only adds the argv surface."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update({"JAX_PLATFORMS": "cpu",
                "PYTHONPATH": REPO + os.pathsep
                + env.get("PYTHONPATH", "")})
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "ptpu_tune.py"),
         "kernels", "--smoke", "--ops", "xent,seq", "--no-crossover",
         "--repeats", "1", "--store", str(tmp_path), "--json"],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["store"] == str(tmp_path)
    assert any(sig.startswith("kernel:xent/") for sig in rec["entries"])
    assert any(sig.startswith("kernel:seq/") for sig in rec["entries"])
    # the recorded entries parse back through the store API
    st = TuningStore(root=str(tmp_path))
    assert len(st.entries()) == 2
