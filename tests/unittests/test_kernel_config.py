"""ops/kernel_config.py, the one module that decides which kernel runs
and at which tile: the PADDLE_TPU_PALLAS parse, the flash-or-dense rule,
the tile table the kernel wrappers resolve from (the lowering rules pass
none), and trace_env_key(), which carries the two variables that are read
at trace time into every compiled-program cache key."""
import builtins
import os
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.core import registry
from paddle_tpu.ops import kernel_config as kc
from paddle_tpu.ops import pallas_kernels as pk


def _set(monkeypatch, name, value):
    if value is None:
        monkeypatch.delenv(name, raising=False)
    else:
        monkeypatch.setenv(name, value)


@pytest.fixture
def pallas_calls(monkeypatch):
    """Keyword arguments of every pl.pallas_call made, in order."""
    seen = []
    real = pk.pl.pallas_call

    def spy(kernel, *args, **kwargs):
        seen.append(dict(kwargs, kernel=kernel))
        return real(kernel, *args, **kwargs)

    monkeypatch.setattr(pk.pl, "pallas_call", spy)
    return seen


# ---------------------------------------------------------------------------
# the two decision tables
# ---------------------------------------------------------------------------

FLASH, DENSE = True, False


@pytest.mark.parametrize("platform,pallas,min_seq,q_len,want", [
    # what the benchmark's cells run: a TPU, nothing set (PERF.md section 4)
    ("tpu", None, None, 256, DENSE),
    ("tpu", None, None, 2048, FLASH),
    ("tpu", None, None, 4096, FLASH),
    # either side of DEFAULT_FLASH_MIN_SEQ
    ("tpu", None, None, 1023, DENSE),
    ("tpu", None, None, 1024, FLASH),
    # one query row (decode) is dense even under the flash-always pin
    ("tpu", None, "0", 1, DENSE),
    ("tpu", None, "0", 0, DENSE),
    ("tpu", None, "0", 2, FLASH),
    # a symbolic length: flash unless opted out
    ("tpu", None, None, None, FLASH),
    ("tpu", "0", None, None, DENSE),
    # what chip_smoke.py --tiny sets
    ("cpu", "1", "32", 31, DENSE),
    ("cpu", "1", "32", 32, FLASH),
    # the opt-out beats the length and the pin
    ("tpu", "0", None, 4096, DENSE),
    ("tpu", "xent,ln", "0", 4096, DENSE),
    # naming 'attn' is no opt-in below the crossover
    ("tpu", "attn", None, 256, DENSE),
    ("tpu", "attn,xent", None, 2048, FLASH),
    # the platform is not asked: off a TPU the kernel is interpreted
    ("cpu", None, None, 2048, FLASH),
    ("cpu", None, None, 256, DENSE),
    # a pin that is no number is the constant
    ("tpu", None, "many", 1023, DENSE),
    ("tpu", None, "many", 1024, FLASH),
])
def test_flash_at_decision_table(monkeypatch, platform, pallas, min_seq,
                                 q_len, want):
    monkeypatch.setattr(kc, "dispatch_platform", lambda: platform)
    _set(monkeypatch, "PADDLE_TPU_PALLAS", pallas)
    _set(monkeypatch, "FLAGS_flash_min_seq", min_seq)
    assert kc.flash_at(q_len) is want


ON, OFF = True, False


@pytest.mark.parametrize("platform,flag,op,want", [
    # nothing set: every kernel on a TPU, none on the CPU
    ("tpu", None, "attn", ON), ("cpu", None, "attn", OFF),
    ("tpu", None, "xent", ON), ("cpu", None, "xent", OFF),
    ("tpu", None, "ln", ON), ("cpu", None, "ln", OFF),
    ("tpu", None, "lstm", ON), ("cpu", None, "lstm", OFF),
    ("tpu", None, "seq", ON), ("cpu", None, "seq", OFF),
    # the explicit forms win over the platform, both ways
    ("tpu", "0", "xent", OFF), ("tpu", "false", "ln", OFF),
    ("tpu", "False", "seq", OFF),
    ("cpu", "1", "lstm", ON), ("cpu", "true", "xent", ON),
    ("cpu", "True", "ln", ON),
    # an allowlist: exactly the named ops, whatever the platform
    ("cpu", "attn,xent", "attn", ON), ("cpu", "attn,xent", "xent", ON),
    ("tpu", "attn,xent", "ln", OFF), ("tpu", "attn,xent", "lstm", OFF),
    ("tpu", "attn, xent", "seq", OFF), ("cpu", " ln ,", "ln", ON),
])
def test_pallas_on_decision_table(monkeypatch, platform, flag, op, want):
    monkeypatch.setattr(kc, "dispatch_platform", lambda: platform)
    _set(monkeypatch, "PADDLE_TPU_PALLAS", flag)
    assert kc.pallas_on(op) is want
    assert kc.pallas_explicit(op) is (None if flag is None else want)


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


def test_flash_min_seq_resolution(monkeypatch):
    monkeypatch.delenv("FLAGS_flash_min_seq", raising=False)
    assert kc.flash_min_seq() == kc.DEFAULT_FLASH_MIN_SEQ
    monkeypatch.setenv("FLAGS_flash_min_seq", "64")
    assert kc.flash_min_seq() == 64
    monkeypatch.setenv("FLAGS_flash_min_seq", "0")
    assert kc.flash_min_seq() == 0


# ---------------------------------------------------------------------------
# the tile table is the one place a block size comes from
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t,d", [(2048, 64), (4096, 128)],
                         ids=["t2048_d64", "t4096_d128"])
def test_default_attn_tiles_are_the_sweeps_choice(pallas_calls, t, d):
    """PERF.md section 6, PR 27: 512 x 512 won the v5e sweep at both shapes
    the benchmark's cells run, D=64 and D=128 alike, so a call that names
    no block runs at that pair whatever the head width."""
    q = jax.ShapeDtypeStruct((1, t, 1, d), jnp.bfloat16)
    jax.eval_shape(lambda q: pk.flash_attention(q, q, q, causal=True), q)
    fwd = pallas_calls[0]
    assert fwd["name"] == "ptpu_flash_fwd"
    assert fwd["grid"] == (1, t // 512)
    assert fwd["in_specs"][0].block_shape == (1, 512, d)
    assert fwd["kernel"].keywords["block_k"] == 512


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _whole_batch(b):
    return lambda block_b: block_b or -(-b // 8) * 8


# op type -> (inputs, attrs, DEFAULT_TILES key, knob, axis of the first
# operand's block that the knob sets, another value for the knob, the
# block a knob value gives at these shapes)
_RULES = {
    "fused_attention": (
        {"Q": [_f32(2, 16, 2, 8)], "K": [_f32(2, 16, 2, 8)],
         "V": [_f32(2, 16, 2, 8)]}, {}, "attn", "block_q", 1, 8,
        lambda block_q: min(block_q, 16)),
    "layer_norm": (
        {"X": [_f32(4, 8, 16)], "Scale": [_f32(16)], "Bias": [_f32(16)]},
        {"begin_norm_axis": 2}, "ln", "block_n", 0, 16, int),
    "softmax_with_cross_entropy": (
        {"Logits": [_f32(32, 10)], "Label": [_i32(32, 1)]}, {},
        "xent", "block_n", 0, 16, int),
    "sequence_pool": (
        {"X": [_f32(32, 6, 4)], "XLen": [_i32(32)]},
        {"pooltype": "AVERAGE"}, "seq", "block_n", 0, 16, int),
    "sequence_softmax": (
        {"X": [_f32(32, 6)], "XLen": [_i32(32)]}, {},
        "seq", "block_n", 0, 16, int),
    "lstm": (
        {"Input": [_f32(12, 5, 16)], "Weight": [_f32(4, 16)],
         "Bias": [_f32(1, 16)], "XLen": [_i32(12)]},
        {"use_peepholes": False}, "lstm", "block_b", 1, 8,
        _whole_batch(12)),
    "lstmp": (
        {"Input": [_f32(12, 5, 16)], "Weight": [_f32(3, 16)],
         "ProjWeight": [_f32(4, 3)], "Bias": [_f32(1, 16)],
         "XLen": [_i32(12)]},
        {"use_peepholes": False}, "lstm", "block_b", 1, 8,
        _whole_batch(12)),
}


@pytest.mark.parametrize("op_type", sorted(_RULES))
def test_lowering_uses_the_tables_tile(monkeypatch, pallas_calls, op_type):
    """Each of the seven rules that reach a kernel names no block: the
    pallas_call it reaches runs at the DEFAULT_TILES entry, and another
    entry there is another block in the call."""
    ins, attrs, op, knob, axis, other, block_at = _RULES[op_type]
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "1")
    monkeypatch.setenv("FLAGS_flash_min_seq", "0")
    rule = registry.get(op_type)
    assert rule.calls_pallas
    ctx = types.SimpleNamespace(mesh=None, amp=False)

    def block_seen():
        del pallas_calls[:]
        jax.eval_shape(lambda ins: rule.lower(ctx, ins, attrs), ins)
        assert pallas_calls, "%s reached no pallas_call" % op_type
        return pallas_calls[0]["in_specs"][0].block_shape[axis]

    assert block_seen() == block_at(kc.DEFAULT_TILES[op][knob])
    assert other != kc.DEFAULT_TILES[op][knob]
    monkeypatch.setitem(kc.DEFAULT_TILES, op,
                        dict(kc.DEFAULT_TILES[op], **{knob: other}))
    assert block_seen() == block_at(other) == other


# ---------------------------------------------------------------------------
# trace_env_key: the environment and the jax config, and no file
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("moved,name,value", [
    (True, "FLAGS_conv_layout", "NHWC"),
    (True, "FLAGS_flash_min_seq", "64"),
    (True, "FLAGS_remat_segment_len", "12"),
    (True, "PADDLE_TPU_PALLAS", "attn,ln"),
    (True, "jax_threefry_partitionable", None),
    (False, "FLAGS_kernel_store_dir", "/nonexistent"),
    (False, "JAX_COMPILATION_CACHE_DIR", "/nonexistent"),
])
def test_trace_env_key_touches_no_file(monkeypatch, moved, name, value):
    """Both executors call trace_env_key() on every run: it reads the
    environment and the jax config and nothing on disk, and it moves when,
    and only when, one of its five inputs does."""
    from paddle_tpu.core.lowering import trace_env_key
    for var in ("FLAGS_conv_layout", "FLAGS_flash_min_seq",
                "FLAGS_remat_segment_len", "PADDLE_TPU_PALLAS"):
        monkeypatch.delenv(var, raising=False)
    trace_env_key()                     # imports done before files go away

    def no_files(*args, **kwargs):
        raise AssertionError("trace_env_key() touched a file: %r" % (args,))

    for mod, fn in ((os, "stat"), (os, "listdir"), (os, "scandir"),
                    (builtins, "open")):
        monkeypatch.setattr(mod, fn, no_files)
    key0 = trace_env_key()
    assert len(key0) == 5
    if name == "jax_threefry_partitionable":
        was = bool(jax.config.jax_threefry_partitionable)
        jax.config.update(name, not was)
        try:
            key1 = trace_env_key()
        finally:
            jax.config.update(name, was)
    else:
        monkeypatch.setenv(name, value)
        key1 = trace_env_key()
        monkeypatch.delenv(name)
    assert (key1 != key0) is moved
    assert trace_env_key() == key0


# ---------------------------------------------------------------------------
# the rule end to end, through the Executor
# ---------------------------------------------------------------------------

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
