"""The `OpDef.infer`s that write a rule's output shapes down (PR 53) against
what tracing the rule gives.

A rule that holds a kernel, and batch_norm, no longer run under
`jax.eval_shape` when a Program is built: `registry.shapes_from` and
`ops/nn_ops._fused_attention_infer` say their outputs' shapes and dtypes from
the input Variables and the attrs. An `infer` has no parameter to adapt: it
equals `abstract_eval`'s answer or it is wrong. Each case here appends one op
at a shape a cell of BENCHMARK.json builds it at, reads what the `infer`
declared (public shape, the two sentinel shapes, dtype), takes the `infer` off
the OpDef and holds the declaration to `abstract_eval` slot for slot. AMP is a
Program's flag and the kernels' paths an environment variable; neither may
change a declaration, so every case runs under both values of both.
"""
import os
import subprocess
import sys

import pytest

import paddle_tpu as fluid
from paddle_tpu.core import registry
from paddle_tpu.observability.registry import REGISTRY

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
F32, BF16, I32 = "float32", "bfloat16", "int32"
INFERRED = ("fused_attention", "layer_norm", "softmax_with_cross_entropy",
            "batch_norm", "mhc_pre", "mhc_post", "mhc_expand", "mhc_reduce",
            "gated_delta_rule", "causal_conv1d", "moe_ffn", "selective_scan",
            "ssd_scan", "rotary_embedding", "kda_delta_rule", "rms_norm")


def _attention(t, hq, hkv, d, dv=None, rope=None, batch=-1, dtype=F32,
               kv_len=False, **attrs):
    ins = {"Q": ((batch, t, hq, d), dtype), "K": ((batch, t, hkv, d), dtype),
           "V": ((batch, t, hkv, dv or d), dtype)}
    if rope:
        ins.update(QRope=((batch, t, hq, rope), dtype),
                   KRope=((batch, t, 1, rope), dtype))
    if kv_len:
        ins["KVLen"] = ((batch, 1), I32)
    attrs = dict({"causal": True, "scale": None, "sp_impl": "ring"}, **attrs)
    return "fused_attention", ins, ("Out",), attrs


def _batch_norm(c, hw, is_test=False, batch=-1):
    stat = ((c,), F32)
    return ("batch_norm",
            {"X": ((batch, c, hw, hw), F32), "Scale": stat, "Bias": stat,
             "Mean": stat, "Variance": stat},
            ("Y", "MeanOut", "VarianceOut", "SavedMean", "SavedVariance"),
            {"epsilon": 1e-5, "momentum": 0.9, "is_test": is_test,
             "data_layout": "NCHW"})


def _layer_norm(shape, begin, dtype=F32):
    width = ((shape[-1],), F32)
    return ("layer_norm", {"X": (shape, dtype), "Scale": width, "Bias": width},
            ("Y", "Mean", "Variance"),
            {"begin_norm_axis": begin, "epsilon": 1e-5})


def _xent(shape, soft=False, outs=("Softmax", "Loss")):
    label = (shape, F32) if soft else (shape[:-1] + (1,), I32)
    return ("softmax_with_cross_entropy",
            {"Logits": (shape, F32), "Label": label}, outs,
            {"soft_label": soft})


def _mhc(kind, batch=-1, dtype=F32, t=4096, c=3584, n=4):
    lead = (batch, t)
    attrs = {"streams": n}
    if kind == "mhc_pre":
        k = n * n + 2 * n
        return (kind, {"X": (lead + (n * c,), dtype), "Phi": ((n * c, k), F32),
                       "Bias": ((k,), F32), "Alpha": ((3,), F32)},
                ("Out", "Coef", "Stream"),
                dict(attrs, sinkhorn_iters=20, epsilon=1e-6, clamp_min=-30.0,
                     clamp_max=30.0))
    if kind == "mhc_post":
        return (kind, {"X": (lead + (n * c,), dtype), "Y": (lead + (c,), dtype),
                       "Coef": (lead + (128,), F32)}, ("Out",), attrs)
    width = c if kind == "mhc_expand" else n * c
    return kind, {"X": (lead + (width,), dtype)}, ("Out",), attrs


def _delta_rule(batch=-1, t=4096, hk=16, hv=32, d=128):
    return ("gated_delta_rule",
            {"Q": ((batch, t, hk, d), F32), "K": ((batch, t, hk, d), F32),
             "V": ((batch, t, hv, d), F32), "G": ((batch, t, hv), F32),
             "Beta": ((batch, t, hv), F32)}, ("Out",), {})


def _kda_rule(batch=-1, t=4096, h=8, d=128):
    return ("kda_delta_rule",
            {"Q": ((batch, t, h, d), F32), "K": ((batch, t, h, d), F32),
             "V": ((batch, t, h, d), F32), "G": ((batch, t, h, d), F32),
             "Beta": ((batch, t, h), F32)}, ("Out",), {})


def _conv(t, c, k, act=None, batch=-1):
    return ("causal_conv1d", {"X": ((batch, t, c), F32), "Filter": ((c, k), F32)},
            ("Out",), {"activation": act} if act else {})


def _scan(t, c, n, batch=-1, dtype=F32):
    return ("selective_scan",
            {"X": ((batch, t, c), dtype), "Delta": ((batch, t, c), F32),
             "A": ((c, n), F32), "B": ((batch, t, n), F32),
             "C": ((batch, t, n), F32), "D": ((c,), F32)}, ("Out",), {})


def _ssd(t, h, p, n, batch=-1, dtype=F32):
    return ("ssd_scan",
            {"X": ((batch, t, h, p), dtype), "Delta": ((batch, t, h), F32),
             "A": ((h,), F32), "B": ((batch, t, n), dtype),
             "C": ((batch, t, n), dtype), "D": ((h,), F32)}, ("Out",), {})


def _rotary(t, h, d, batch=-1, dtype=F32, **attrs):
    return ("rotary_embedding",
            {"X": ((batch, t, h, d), dtype), "Pos": ((batch, t), I32)},
            ("Out",), dict({"base": 1e6}, **attrs))


def _rms_norm(shape, begin, scale, dtype=F32, gate=False, **attrs):
    ins = {"X": (shape, dtype), "Scale": ((scale,), F32)}
    if gate:
        ins["Gate"] = (shape, dtype)
    return ("rms_norm", ins, ("Y",),
            dict({"begin_norm_axis": begin, "epsilon": 1e-6}, **attrs))


def _moe_ffn(t, d, f, experts, held, top_k, **attrs):
    return ("moe_ffn",
            {"X": ((-1, t, d), F32), "Router": ((d, experts), F32),
             "WGate": ((held, d, f), F32), "WUp": ((held, d, f), F32),
             "WDown": ((held, f, d), F32)},
            ("Out", "BalanceLoss", "ZLoss", "ExpertLoad"),
            dict({"top_k": top_k, "norm_topk_prob": False}, **attrs))


# one case an (op, shape): the cell that builds it is in the name
CASES = {
    "attention-t2048-8x64-lens": _attention(2048, 8, 8, 64, causal=False,
                                            kv_len=True),
    "attention-t2048-8x64-causal": _attention(2048, 8, 8, 64, kv_len=True),
    "attention-t256-8x64-dense": _attention(256, 8, 8, 64, kv_len=True),
    "attention-olmoe-16x128": _attention(4096, 16, 16, 128),
    "attention-smallthinker-7on1-window": _attention(8192, 7, 1, 128,
                                                     window=4096),
    "attention-qwen3next-16on2-at256": _attention(4096, 16, 2, 256),
    "attention-lfm2-32on8-at64": _attention(8192, 32, 8, 64),
    "attention-xing-latent-128+64on128": _attention(
        4096, 32, 32, 128, rope=64, scale=0.14467962580268923),
    "attention-glm-latent-192+64on256": _attention(
        4096, 20, 20, 192, dv=256, rope=64, scale=0.0625),
    "attention-glm-latent-dense-t512": _attention(
        512, 20, 20, 192, dv=256, rope=64, scale=0.0625),
    "attention-static-batch": _attention(4096, 16, 16, 128, batch=2),
    "attention-bfloat16": _attention(2048, 8, 8, 64, dtype=BF16),
    "attention-decode-one-row": _attention(1, 8, 8, 64, batch=4),
    "layer_norm-t2048": _layer_norm((-1, 2048, 512), 2),
    "layer_norm-t256": _layer_norm((-1, 256, 512), 2),
    "layer_norm-axis1-static": _layer_norm((8, 1024), 1),
    "layer_norm-bfloat16": _layer_norm((-1, 256, 512), 2, BF16),
    "xent-transformer": _xent((-1, 32000)),
    "xent-olmoe": _xent((-1, 50304)),
    "xent-3d-static": _xent((2, 128, 1000)),
    "xent-soft-labels": _xent((-1, 1000), soft=True),
    "xent-loss-alone": _xent((-1, 16384), outs=("Loss",)),
    "batch_norm-train-64x112": _batch_norm(64, 112),
    "batch_norm-train-2048x7": _batch_norm(2048, 7),
    "batch_norm-test-256x14": _batch_norm(256, 14, is_test=True),
    "batch_norm-train-static-batch": _batch_norm(512, 28, batch=256),
    "mhc_pre-xing": _mhc("mhc_pre"),
    "mhc_pre-xing-static-bfloat16": _mhc("mhc_pre", batch=1, dtype=BF16),
    "mhc_post-xing": _mhc("mhc_post"),
    "mhc_post-xing-static-bfloat16": _mhc("mhc_post", batch=1, dtype=BF16),
    "mhc_expand-xing": _mhc("mhc_expand"),
    "mhc_reduce-xing": _mhc("mhc_reduce"),
    "mhc_reduce-odd-width": _mhc("mhc_reduce", t=16, c=24, n=2),
    "gated_delta_rule-qwen3next": _delta_rule(),
    "gated_delta_rule-static-batch": _delta_rule(batch=2),
    "kda_delta_rule-ling": _kda_rule(),
    "kda_delta_rule-static-batch": _kda_rule(batch=1, t=100),
    "causal_conv1d-qwen3next-silu": _conv(4096, 8192, 4, "silu"),
    "causal_conv1d-lfm2": _conv(8192, 2048, 3),
    "causal_conv1d-off-the-kernel": _conv(100, 96, 3, batch=2),
    "selective_scan-phi4flash": _scan(8192, 5120, 16),
    "selective_scan-phi4flash-static-bfloat16": _scan(8192, 5120, 16,
                                                      batch=1, dtype=BF16),
    "selective_scan-off-the-kernel": _scan(40, 96, 4, batch=2),
    "ssd_scan-granite": _ssd(2048, 64, 64, 128),
    "ssd_scan-granite-static-bfloat16": _ssd(2048, 64, 64, 128, batch=1,
                                             dtype=BF16),
    "ssd_scan-off-the-kernel": _ssd(40, 3, 24, 8, batch=2),
    "rotary-sdar-q-32x128": _rotary(8192, 32, 128),
    "rotary-sdar-k-static-bfloat16": _rotary(8192, 4, 128, batch=1,
                                             dtype=BF16),
    "rotary-laguna-yarn-64-of-128": _rotary(
        4096, 12, 128, rotary_dim=64, table_scale=1.4852030263919618,
        inv_freq=[0.5 ** i for i in range(32)]),
    "rotary-qwen3next-64-of-256": _rotary(4096, 16, 256, rotary_dim=64),
    "rotary-glm-interleaved-64": _rotary(4096, 20, 64, layout="interleaved"),
    # PR 72: the rule holds a kernel where the norm is over a head
    "rms_norm-sdar-q-32x128": _rms_norm((-1, 8192, 32, 128), 3, 128),
    "rms_norm-sdar-k-static-bfloat16": _rms_norm((1, 8192, 4, 128), 3, 128,
                                                 BF16),
    "rms_norm-qwen3next-zero-centred-256": _rms_norm(
        (-1, 4096, 16, 256), 3, 256, zero_centered=True),
    "rms_norm-qwen3next-gated": _rms_norm((-1, 4096, 32, 128), 3, 128,
                                          gate=True),
    "rms_norm-block-3d": _rms_norm((-1, 4096, 2048), 2, 2048),
    "rms_norm-a-weight-a-group": _rms_norm((-1, 2048, 8, 512), 3, 4096,
                                           begin_scale_axis=2),
    # PR 50's infer, a table since PR 53
    "moe_ffn-olmoe-64-all-held": _moe_ffn(4096, 2048, 1024, 64, 64, 8),
    "moe_ffn-smallthinker-64-a-quarter-held": _moe_ffn(
        8192, 2560, 768, 64, 16, 6, first_expert=16, norm_topk_prob=True),
}


def _append(case, amp=False):
    """The case's op appended to a fresh Program: its inputs declared, its
    outputs bare, so what they hold afterwards is the infer's alone."""
    op_type, ins, outs, attrs = case
    main = fluid.Program()
    main._amp = amp
    block = main.global_block()
    inputs = {slot: [block.create_var(name="in_" + slot, shape=shape,
                                      dtype=dtype)]
              for slot, (shape, dtype) in ins.items()}
    outputs = {slot: [block.create_var(name="out_" + slot, dtype=None)]
               for slot in outs}
    op = block.append_op(type=op_type, inputs=inputs, outputs=outputs,
                         attrs=dict(attrs))
    return block, op, outputs


def _traced(block, op, monkeypatch):
    monkeypatch.setattr(registry.get(op.type), "infer", None)
    res = registry.abstract_eval(block, op)
    assert res is not None, "the rule does not trace at this case"
    return res


@pytest.mark.parametrize("kernels", ["", "1"], ids=["default", "kernels"])
@pytest.mark.parametrize("amp", [False, True], ids=["fp32", "amp"])
@pytest.mark.parametrize("name", sorted(CASES))
def test_infer_declares_what_tracing_the_rule_gives(name, amp, kernels,
                                                    monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS", kernels)
    assert registry.get(CASES[name][0]).infer is not None
    block, op, outputs = _append(CASES[name], amp)
    res = _traced(block, op, monkeypatch)
    assert sorted(res) == sorted(outputs)
    for slot, (var,) in outputs.items():
        (public, sentinels, dtype), = res[slot]
        assert (var.shape, var.dtype) == (public, dtype), slot
        assert var._abstract_shapes == sentinels + (public,), slot


def test_a_folded_batch_product_stays_one():
    """[-1, T, D] reshaped to [-1, D] is B * T rows under both sentinels;
    an infer that read the public -1 alone would call it B."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[256, 512], dtype="float32")
        rows = fluid.layers.reshape(x, shape=[-1, 512])
        y = fluid.layers.layer_norm(rows, begin_norm_axis=1)
    a, b = registry.BATCH_SENTINEL, registry.BATCH_SENTINEL_B
    assert y._abstract_shapes == ((a * 256, 512), (b * 256, 512), (-1, 512))
    mean = main.global_block().var_recursive(y.op.outputs["Mean"][0])
    assert mean._abstract_shapes == ((a * 256,), (b * 256,), (-1,))
    with fluid.program_guard(main, startup):
        back = fluid.layers.reshape(y, shape=[-1, 256, 512])
    assert back.shape == (-1, 256, 512)


# --------------------------------------------- the refusal stays at build --
def test_flash_attention_refuses_a_latent_width_when_the_op_is_appended():
    with pytest.raises(ValueError) as err:
        _append(_attention(4096, 20, 20, 192, dv=128, rope=64))
    assert "192 + 64 on 128" in str(err.value)


def test_the_refusal_is_the_flash_kernels_own():
    """Under the crossover the dense path joins the parts at any widths, so
    the same op builds (and lowers) there."""
    _, _, outputs = _append(_attention(512, 20, 20, 192, dv=128, rope=64))
    assert outputs["Out"][0].shape == (-1, 512, 20, 128)


# --------------------------------------- the two claimed cells' Programs --
def _samples(how):
    return {dict(key)["op"]: n for key, n in REGISTRY.counter(
        "ptpu_infer_shape_calls_total").samples() if dict(key)["how"] == how}


@pytest.mark.parametrize("cell_name", ["transformer_base_train_t2048",
                                       "xing4_0_29b_a4b_train_1seq"])
def test_a_cell_s_build_traces_none_of_the_inferred_rules(cell_name):
    sys.path.insert(0, REPO)
    from benchmark import manifest
    cell = manifest.load_cell(os.path.join(REPO, "BENCHMARK.json"), cell_name)
    traced, custom = _samples("eval_shape"), _samples("custom")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        cell.config_module.build(fluid, cell.config, cell.traffic)
    built = {op.type for block in main.blocks for op in block.ops}
    assert built & set(INFERRED)
    for op_type in INFERRED:
        assert _samples("eval_shape").get(op_type, 0) \
            == traced.get(op_type, 0), op_type
        if op_type in built:
            assert _samples("custom")[op_type] > custom.get(op_type, 0)


def test_no_infer_imports_pallas():
    """In a process of its own: every case's op appended, and
    `jax.experimental.pallas` (1.4-1.6 s on the benchmark's hosts) never
    entered. The one case left out is the latent head whose value is not as
    wide as its part without position, on the flash path: its infer asks
    `pallas_kernels.latent_form`, the kernels' own check, in a cell where
    `lookup_table` has imported a kernel module before it."""
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from tests.unittests import test_infer_parity as t\n"
        "for name, case in sorted(t.CASES.items()):\n"
        "    if name != 'attention-glm-latent-192+64on256':\n"
        "        t._append(case)\n"
        "assert 'jax.experimental.pallas' not in sys.modules\n"
        "print(len(t.CASES))\n" % REPO)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PADDLE_TPU_PALLAS="")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert int(out.stdout.split()[-1]) == len(CASES)
