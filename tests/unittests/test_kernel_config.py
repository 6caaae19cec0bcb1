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
    """Keyword arguments of every pl.pallas_call made, in order. A kernel
    entry is a jax.jit that keeps its trace under its arguments (PR 60,
    ops/pallas_import.py): the traces of earlier tests are dropped, so that
    a call at a shape they ran reaches pl.pallas_call again."""
    jax.clear_caches()
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
    q = jax.ShapeDtypeStruct((1, t, 2, d), jnp.bfloat16)
    jax.eval_shape(lambda q: pk.flash_attention(q, q, q, causal=True), q)
    fwd = pallas_calls[0]
    assert fwd["name"] == "ptpu_flash_fwd"
    # (sequence, block of heads, q block, head in its block): two heads of
    # 64 share a 128-lane block, a head of 128 has one to itself
    per_block = 128 // d
    assert fwd["grid"] == (1, 2 // per_block, t // 512, per_block)
    assert fwd["in_specs"][0].block_shape == (512, 128)
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
         "V": [_f32(2, 16, 2, 8)]}, {}, "attn", "block_q", 0, 8,
        lambda block_q: min(block_q, 16)),
    # the table holds bytes of one float32 tile, not rows: 64 a row here,
    # and never more rows than the 32 there are
    "layer_norm": (
        {"X": [_f32(4, 8, 16)], "Scale": [_f32(16)], "Bias": [_f32(16)]},
        {"begin_norm_axis": 2}, "ln", "tile_bytes", 0, 1024,
        lambda tile_bytes: min(tile_bytes // 64, 32)),
    # bytes again (PR 44): 40 a float32 row here; 1024 of them are 24 rows,
    # and the 16 that divide N lie within a factor of two below
    "softmax_with_cross_entropy": (
        {"Logits": [_f32(32, 10)], "Label": [_i32(32, 1)]}, {},
        "xent", "tile_bytes", 0, 1024,
        lambda tile_bytes: 32 if tile_bytes >= 32 * 40 else 16),
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

    first = block_seen()
    assert first == block_at(kc.DEFAULT_TILES[op][knob])
    assert other != kc.DEFAULT_TILES[op][knob]
    monkeypatch.setitem(kc.DEFAULT_TILES, op,
                        dict(kc.DEFAULT_TILES[op], **{knob: other}))
    assert block_seen() == block_at(other) != first


_BF16 = jnp.bfloat16


@pytest.mark.parametrize("n,d,dtype,block_n,grid,rows", [
    # both transformer cells: 32 grid steps a call where 8 rows took 2048
    (16384, 512, jnp.float32, None, 32, 512),
    (16384, 512, _BF16, None, 32, 512),
    # other widths keep the bytes and change the rows
    (16384, 1024, jnp.float32, None, 64, 256),
    (16384, 8192, jnp.float32, None, 512, 32),
    # a row wider than the whole budget: one sublane granule all the same
    (64, 65536, jnp.float32, None, 8, 8),
    (64, 65536, _BF16, None, 4, 16),
    # fewer rows than one tile (a decode step's [B, D]): one padded tile
    (4, 512, jnp.float32, None, 1, 8),
    (4, 512, _BF16, None, 1, 16),
    (40, 512, jnp.float32, None, 1, 40),
    # no divisor of N near the budget: whole tiles and a padded tail
    (1000, 512, jnp.float32, None, 2, 512),
    (3000, 512, jnp.float32, None, 6, 512),
    # a divisor within a factor of two below: no pad, no slice
    (1200, 512, jnp.float32, None, 3, 400),
    (12288, 512, jnp.float32, None, 24, 512),
    # D not a multiple of 128; bf16 rows come in sixteens
    (100, 520, _BF16, None, 1, 112),
    (1000, 520, jnp.float32, None, 2, 504),
    (40, 8192, _BF16, None, 2, 32),
    # what a sweep or a kernel test names wins over the table
    (16384, 512, jnp.float32, 8, 2048, 8),
    (1000, 512, jnp.float32, 24, 42, 24),
], ids=lambda v: getattr(v, "__name__", None) or str(v))
def test_layer_norm_tile_follows_the_budget(pallas_calls, n, d, dtype,
                                            block_n, grid, rows):
    """DEFAULT_TILES["ln"] is a budget in bytes for the float32 copy of
    one input tile; the rows of a grid step follow from the N, D and dtype
    the call sees, in whole sublane granules, with no pad where a divisor
    of N is near."""
    assert kc.DEFAULT_TILES["ln"] == {"tile_bytes": 1 << 20}
    out = jax.eval_shape(
        lambda x, s, b: pk.layer_norm(x, s, b, block_n=block_n),
        jax.ShapeDtypeStruct((n, d), dtype), _f32(d), _f32(d))
    call, = pallas_calls
    assert call["name"] == "ptpu_layer_norm_fwd"
    assert call["grid"] == (grid,)
    assert call["in_specs"][0].block_shape == (rows, d)
    assert [o.block_shape for o in call["out_specs"]] == [
        (rows, d), (rows, 1), (rows, 1)]
    # the kernel sees whole tiles; the caller sees its own N and dtype
    assert call["out_shape"][0].shape == (grid * rows, d)
    assert 0 <= grid * rows - n < rows
    if block_n is None:
        assert rows % (16 if dtype == _BF16 else 8) == 0
        assert rows * d * 4 <= max(1 << 20, 8 * d * 4 * (
            2 if dtype == _BF16 else 1))
    assert (out[0].shape, out[0].dtype) == ((n, d), dtype)
    assert out[1].shape == out[2].shape == (n,)


@pytest.mark.parametrize("shape", [(64, 256, 512), (8, 2048, 512)],
                         ids=["t256", "t2048"])
def test_layer_norm_reaches_its_named_kernel_on_a_tpu(monkeypatch,
                                                      pallas_calls, shape):
    """What refused PR 29 (PERF.md section 7): BENCHMARK.json lists
    `layer_norm_ms_per_step` for both transformer cells, and its reader
    leaves the metric out where no Mosaic call named `ptpu_layer_norm_fwd`
    ran. With nothing set and a TPU to dispatch to, the rule at the cells'
    shape reaches exactly that call, once, and the reader takes the name
    the trace gives it."""
    from benchmark import kernel_ms
    monkeypatch.delenv("PADDLE_TPU_PALLAS", raising=False)
    monkeypatch.setattr(kc, "dispatch_platform", lambda: "tpu")
    monkeypatch.setattr(pk, "dispatch_platform", lambda: "tpu")
    rule = registry.get("layer_norm")
    ins = {"X": [_f32(*shape)], "Scale": [_f32(512)], "Bias": [_f32(512)]}
    out = jax.eval_shape(
        lambda ins: rule.lower(types.SimpleNamespace(mesh=None, amp=False),
                               ins, {"begin_norm_axis": 2}), ins)
    assert out["Y"][0].shape == shape
    call, = pallas_calls
    assert call["name"] == "ptpu_layer_norm_fwd" in pk.KERNEL_NAMES
    assert not call["interpret"]
    assert call["grid"] == (32,)
    for op, want in (
            ("ptpu_layer_norm_fwd custom-call tpu_custom_call", True),
            ("ptpu_layer_norm_fwd.17 custom-call tpu_custom_call", True),
            ("jvp_ptpu_layer_norm_fwd_.17 custom-call tpu_custom_call",
             False),
            ("ptpu_layer_norm_fwd.17 fusion kLoop", False)):
        assert kernel_ms._is_kernel(op, call["name"]) is want


# ---------------------------------------------------------------------------
# trace_env_key: the environment and the jax config, and no file
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("moved,name,value", [
    (True, "FLAGS_conv_layout", "NHWC"),
    (True, "FLAGS_flash_min_seq", "64"),
    (True, "PADDLE_TPU_PALLAS", "attn,ln"),
    (True, "jax_threefry_partitionable", None),
    (False, "FLAGS_kernel_store_dir", "/nonexistent"),
    (False, "JAX_COMPILATION_CACHE_DIR", "/nonexistent"),
])
def test_trace_env_key_touches_no_file(monkeypatch, moved, name, value):
    """Both executors call trace_env_key() on every run: it reads the
    environment and the jax config and nothing on disk, and it moves when,
    and only when, one of its four inputs does."""
    from paddle_tpu.core.lowering import trace_env_key
    for var in ("FLAGS_conv_layout", "FLAGS_flash_min_seq",
                "PADDLE_TPU_PALLAS"):
        monkeypatch.delenv(var, raising=False)
    trace_env_key()                     # imports done before files go away

    def no_files(*args, **kwargs):
        raise AssertionError("trace_env_key() touched a file: %r" % (args,))

    for mod, fn in ((os, "stat"), (os, "listdir"), (os, "scandir"),
                    (builtins, "open")):
        monkeypatch.setattr(mod, fn, no_files)
    key0 = trace_env_key()
    assert len(key0) == 4
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
