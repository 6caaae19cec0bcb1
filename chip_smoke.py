"""chip_smoke.py — the quickest proof that the trainer still starts on the chip.

    python chip_smoke.py            # on a TPU host, through the chip tool
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny    # the CPU rehearsal

One process drives the training path — fluid Program -> core/lowering.py ->
Executor(TPUPlace()).run — through the entry points a user calls, at the
full width and depth of the two headline models, on random weights made
from a seed, and checks what comes out by the repo's own means:

  A  ResNet-50 (the BASELINE.json headline): bf16, batch 256, Momentum;
     single steps on one device-resident batch, then one run(steps=K)
     block so the lax.scan lowering compiles too.
  B  the base transformer (the paper's base widths) at T=256 (dense attention,
     Pallas layer_norm + softmax_xent) and T=2048 (flash fwd + both bwd).
  C  every Pallas kernel family, as a one-op Program at the shape and
     dtype its shipped model gives it, forward and backward, against the
     same op's XLA lowering under a written tolerance — and the trace is
     inspected: the kernel was dispatched, and not interpreted on a TPU.
     The gated delta rule's two kernels and its lax.scan path against the
     token-by-token recurrence at the Qwen3-Next cell's shapes.
     One routed expert layer alone at three cells' sizes, and the scalars
     `routed_ffn` moves between the router and the rows at five cells'
     shapes, each site by XLA's gather or scatter (the form up to PR 62,
     kept here) and by the compare or sort the program has, equal to the bit.
  D  (>= 4 devices) ParallelExecutor over a dp=4 mesh on phase A's
     program, replicated and with the ZeRO-sharded weight update.
  E  the timing-barrier premise: K steps timed to jax.block_until_ready
     and to a device->host fetch agree (core/utils.device_fetch_barrier).
  F  causal_conv1d at the Qwen3-Next cell's shape, [1, 4096, 8192] bf16:
     the two Pallas kernels against the rule's jax.numpy passes, forward
     and forward + backward on the host's clock, and the largest error of
     y, dx and dw of either against a float32 recomputation.
  G  a looped decoder (models/causal_lm.py, total_ut_steps 4) at the Ouro
     cell's widths and depth 2: the gradients of weights the four passes
     share, summed through the loop op's transpose, against jax.grad of
     the plain float32 reference, with the loop's recomputation and
     without it. The benchmark's `correct` sees only the forward pass.
  I  the embedding's backward: lookup_table's rule, forward + backward
     alone, at the seven token cells' (rows, vocabulary, width), under
     uniform ids, Zipf ids and one id throughout, beside jnp.take's own
     transpose and the least time the bytes take; each checked against
     float64 sums of the rows on the host.

  J  latent attention and the hyper-connections at the Xing4.0 cell's
     shapes: the three flash kernels at a head of 192 on values of 128
     with the one rotary key all heads share ([1, 4096, 32, 128 | 64]
     bf16), forward, dq, dk, dv, dq_rope and dk_rope against the dense
     float32 attention on the same rounded inputs; the `ptpu_mhc_*` kernels
     on a stream [4096, 4 x 3584] bf16, forward and backward, against the
     plain jax.numpy passes in float32; each part's milliseconds.

  L  the latent core at GLM-4.7-Flash's widths, 20 heads of 192 + 64 on
     values of 256 ([2, 4096, 20, ..] bf16), in two forms: `whole`, the one
     `pallas_kernels.flash_attention` runs such a head in (the two parts
     joined in HBM, the rotary key repeated a head, the plain kernels at 256
     on 256), and `two_part`, made here and nowhere in the tree (the part
     without position padded with zero lanes to 256, then the two-part
     kernels as at equal widths; their dK/dV kernel needs 16.29 MiB of VMEM
     at 512 x 512 and is run at block_k 256), forward, dq, dk, dv, dq_rope
     and dk_rope of each against the dense float32 attention, and each
     form's milliseconds forward and forward + backward, beside the plain
     kernels on heads that arrive joined (what the join costs).

  M  the routed experts' nine grouped matmuls of a layer alone (gate, up
     and down: forward, d rows, d weights), `ops/expert_gmm.py`'s kernels
     against `jax.lax.ragged_dot` and its transposes, at the six expert
     cells' shapes with the groups a seeded router gives and bf16 operands:
     ms and TFLOP/s a pass of each route, the kernels at several row tiles,
     and the largest difference between the routes over the rows in a group.
  N  the selective scan's two kernels alone at the Phi-4-mini-flash cell's
     shape, [1, 8192, 5120] with 16 states: forward and forward + backward
     against the plain `lax.scan` over tokens on this device (every
     gradient), timed; and ONE differential core at the cell's shape in the
     form the builder gives the flash kernels (40 heads of 128 on 20 key
     heads: a pair's two maps, queries and keys padded from 64), under the
     window of 512 and full, against the float32 dense two-map form.
  P  rotary_embedding's one Pallas pass (`ops/rotary_kernels.py`) through
     Mosaic against the rule's jax.numpy lines through XLA, on this device,
     at the q and k shapes of the cells that take it (SDAR, OLMoE, Ouro, SmallThinker,
     Laguna's sliding layers), forward and transpose: EQUAL
     (`array_equal`, or one unit in the last place of bf16 on at most 1
     element in 10,000, the count printed) or the phase fails; ms a call of
     both beside the time the bytes take, and the kernel at the tiles of
     the sweep.
  Q  the delta rule whose decay is a key channel's (`ops/kda_kernels.py`)
     at the Ling-3.0-flash cell's shape, [1, 4096, 8, 128] with bf16
     operands: the two Pallas kernels against the same chunked form under
     `lax.scan` (`path="scan"`), forward and forward + backward: the
     largest error of the output and of each of the five gradients, and ms
     of both paths, under the decay of a layer at its start (channels from
     the bound of -5 a token to near 0) and with every channel at the
     bound; the kernel path against the token-by-token recurrence too.

Every phase that fails makes the exit code non-zero. Timings are printed
for the next reader, labelled with the device; they are not metrics. The
last line of stdout is one JSON object, {"ok": ..., "device": {...}}.
Exit codes: 0 all phases passed, 1 a phase failed, 2 no TPU (nothing ran).
"""
import argparse
import functools
import itertools
import json
import os
import statistics
import sys
import time
import traceback

import numpy as np

FULL = {
    "resnet": dict(model="resnet50", class_dim=1000, hw=224, batch=256,
                   steps=8, scan_steps=4),
    "transformer": dict(n_layer=6, d_model=512, n_head=8, d_inner=2048,
                        vocab=30000, steps=4,
                        # (T, batch): below / above the flash crossover
                        shapes=((256, 32), (2048, 4))),
    "kernels": dict(
        # the transformer's T=2048 cell (two heads of 64 a 128-lane block)
        # and OLMoE's and Ouro's heads of 128, one sequence
        attn=(dict(b=8, t=2048, h=8, d=64), dict(b=1, t=4096, h=16, d=128)),
        # SmallThinker's: 7 query heads on 1 key/value head of 128, a
        # window of half the sequence
        attn_window=dict(b=1, t=4096, h=7, hkv=1, d=128, window=2048),
        moe=dict(n=4096, d=512, f=256),
        # one expert layer of the SmallThinker and Qwen3-Next cells: tokens,
        # hidden, experts routed over and held, their width, experts a token
        moe_layers=(
            dict(name="SmallThinker", n=8192, d=2560, e=64, held=16, f=768,
                 top_k=6, activation="relu", scoring="softmax"),
            dict(name="Qwen3-Next", n=4096, d=2048, e=512, held=32, f=512,
                 top_k=10, activation="silu", scoring="softmax"),
            # Nemotron-3-Super's: a share narrower than top_k, ungated
            # experts in a latent of 1024 under a router that reads 4096
            dict(name="Nemotron-3-Super", n=4096, d=1024, router_d=4096,
                 e=512, held=8, f=2688, top_k=22, gated=False,
                 activation="relu2", scoring="sigmoid")),
        # the rows no group reads (PR 65), at six cells' shapes: the unit as
        # the gate/up kernel's epilogue, what a buffer of sorted rows starts
        # from, the layer before and after
        dead_rows=(
            dict(name="Nemotron-3-Super", n=4096, d=1024, router_d=4096,
                 e=512, held=8, f=2688, top_k=22, gated=False,
                 activation="relu2", scoring="sigmoid"),
            dict(name="Laguna-S-2.1", n=4096, d=3072, e=256, held=8, f=1024,
                 top_k=10, activation="silu", scoring="softmax"),
            dict(name="LFM2", n=8192, d=2048, e=32, held=8, f=1792, top_k=4,
                 activation="silu", scoring="sigmoid"),
            dict(name="SmallThinker", n=8192, d=2560, e=64, held=16, f=768,
                 top_k=6, activation="relu", scoring="softmax"),
            dict(name="Qwen3-Next", n=4096, d=2048, e=512, held=32, f=512,
                 top_k=10, activation="silu", scoring="softmax"),
            dict(name="OLMoE", n=16384, d=2048, e=64, held=64, f=1024,
                 top_k=8, activation="silu", scoring="softmax")),
        # the scalars `routed_ffn` moves between the router and the rows, at
        # five cells' shapes: tokens, experts routed over and held, experts
        # a token (PR 63: each site's two forms alone)
        routing_sites=(
            dict(name="Nemotron-3-Super", n=4096, e=512, held=8, top_k=22),
            dict(name="LFM2", n=8192, e=32, held=8, top_k=4),
            dict(name="SmallThinker", n=8192, e=64, held=16, top_k=6),
            dict(name="Qwen3-Next", n=4096, e=512, held=32, top_k=10),
            dict(name="OLMoE", n=16384, e=64, held=64, top_k=8)),
        # Qwen3-Next's gated delta rule at its cell's shapes: one sequence
        # of 4096, 16 key heads on 32 value heads of 128
        gated_delta=dict(b=1, t=4096, hk=16, hv=32, d=128),
        # its convolution: q, k and v of a delta layer side by side
        causal_conv=dict(b=1, t=4096, c=8192, width=4),
        xent=dict(n=8192, v=30000),                 # its [B*T, vocab] loss
        # its d_model rows: the benchmark cells' [16384, 512], whole tiles
        # at the table's budget, and an N that leaves a padded tail
        ln=(dict(b=8, t=2048, d=512), dict(b=3, t=1000, d=512)),
        # dynamic_lstm hidden sizes of stacked_lstm (bench: 512/4),
        # language_model (64) and machine_translation (32)
        lstm=(dict(b=128, t=64, d=128, reverse=True),
              dict(b=32, t=32, d=64, reverse=False),
              dict(b=32, t=16, d=32, reverse=False)),
        lstmp=dict(b=128, t=64, d=128, p=64),
        seq_softmax=dict(b=64, t=50),               # MT attention scores
        seq_pool=dict(b=128, t=64, f=32)),          # sentiment conv pool
    # the Ouro cell's widths; depth 2, T and the vocabulary cut so that the
    # float32 reference's backward pass fits beside the program
    "looped": dict(hidden_size=2048, num_attention_heads=16,
                   intermediate_size=5632, vocab_size=8192,
                   num_hidden_layers=2, t=2048, tol=5e-2),
    # the LFM2 cell's: one sequence of 8192 at hidden 2048, 3 taps; 8 of 32
    # experts of 1792 held, 4 a token
    "lfm2": dict(t=8192, d=2048, width=3, experts=32, held=8, f=1792,
                 top_k=4),
    # lookup_table at the token cells: (rows, vocabulary, width)
    "embedding": (("transformer_base _t256 and _t2048", 16384, 32000, 512),
                  ("olmoe_1b_7b", 16384, 50304, 2048),
                  ("smallthinker_21b_a3b", 8192, 37984, 2560),
                  ("qwen3_next_80b_a3b", 4096, 18992, 2048),
                  ("ouro_2_6b", 4096, 49152, 2048),
                  ("lfm2_8b_a1b", 8192, 16384, 2048)),
    # the Xing4.0 cell's: one sequence of 4096, 32 heads of 128 + 64 on
    # values of 128; four streams of 3584
    "latent": dict(t=4096, h=32, d=128, dr=64, streams=4, c=3584,
                   iters=20, tol=3e-2),
    # the GLM-4.7-Flash cell's core: two sequences of 4096, 20 heads of 192
    # + 64 on values of 256; the blocks each form runs at
    # the Phi-4-mini-flash cell's scan and differential core
    "selective_scan": dict(b=1, t=8192, c=5120, n=16, tol=2e-3),
    # the granite-4.0-h-micro cell's state-space-dual scan: one sequence of
    # 2048, 64 heads of 64 on 128 states; (chunk, heads a grid step) swept
    "ssd": dict(b=1, t=2048, h=64, p=64, n=128, tol=3e-2, segment=64,
                sweep=((128, 8), (128, 16), (128, 32), (256, 16))),
    # (cell's tensor, B, T, heads, head, base): the whole heads of 128 that
    # rotary_embedding turns in a cell, q then k
    "rope": dict(cases=(("SDAR q", 1, 8192, 32, 128, 1e6),
                        ("SDAR k", 1, 8192, 4, 128, 1e6),
                        ("OLMoE q and k", 4, 4096, 16, 128, 1e4),
                        ("Ouro q and k", 1, 4096, 16, 128, 1e6),
                        ("SmallThinker q", 1, 8192, 7, 128, 1.5e6),
                        ("SmallThinker k", 1, 8192, 1, 128, 1.5e6),
                        ("Laguna sliding q", 1, 4096, 18, 128, 1e4),
                        ("Laguna sliding k", 1, 4096, 2, 128, 1e4),
                        ("a head of 256", 1, 4096, 4, 256, 1e6)),
                 sweep=(1 << 19, 1 << 20, 1 << 21)),
    "kda": dict(b=1, t=4096, h=8, d=128, tol=2e-2, exact_tol=5e-2),
    # (cell's tensor, B, T, heads, head, zero_centered): the heads a cell
    # norms one by one under a weight [head]
    "rms_head": dict(cases=(("SDAR q", 1, 8192, 32, 128, False),
                            ("SDAR k", 1, 8192, 4, 128, False),
                            ("Laguna full q", 1, 4096, 12, 128, False),
                            ("Laguna sliding q", 1, 4096, 18, 128, False),
                            ("Laguna k", 1, 4096, 2, 128, False),
                            ("Qwen3-Next q", 1, 4096, 16, 256, True),
                            ("Qwen3-Next k", 1, 4096, 2, 256, True),
                            ("Ling o_norm", 1, 4096, 8, 128, False),
                            ("Phi-4-mini-flash subln", 1, 8192, 20, 128,
                             False)),
                     sweep=(1 << 19, 1 << 20, 1 << 21)),
    "differential": dict(b=1, t=8192, pairs=20, kv_pairs=10, hd=64,
                         window=512, tol=2e-2),
    "latent_unequal": dict(b=2, t=4096, h=20, d=192, dr=64, dv=256,
                           blocks={"whole": (512, 512),
                                   "two_part": (512, 256)}, tol=3e-2),
    # the output head and its loss at the SmallThinker and OLMoE cells:
    # (rows, width, vocabulary); then a ragged N for the kernels alone
    "head": dict(shapes=((8192, 2560, 37984), (16384, 2048, 50304)),
                 ragged=(1000, 37984), rows=(16, 32, 64)),
    # an expert layer of the six expert cells: tokens, hidden, experts
    # routed over and held, their width, experts a token; the row tiles
    "expert_gmm": dict(
        shapes=(("smallthinker_21b_a3b", 8192, 2560, 64, 16, 768, 6),
                ("lfm2_8b_a1b", 8192, 2048, 32, 8, 1792, 4),
                ("olmoe_1b_7b", 16384, 2048, 64, 64, 1024, 8),
                ("qwen3_next_80b_a3b", 4096, 2048, 512, 32, 512, 10),
                ("xing4_0_29b_a4b", 4096, 3584, 64, 8, 1024, 4),
                ("glm_4_7_flash", 8192, 2048, 64, 8, 1536, 4)),
        block_m=(256, 512, 1024), tol=2e-2),
    "barrier": dict(steps=5, rounds=3, tol=0.15),
    "dp_loss_rtol": 2e-2,
}
TINY = {
    "resnet": dict(model="resnet20", class_dim=10, hw=32, batch=8,
                   steps=4, scan_steps=2),
    "transformer": dict(n_layer=1, d_model=32, n_head=2, d_inner=64,
                        vocab=64, steps=3, shapes=((16, 4), (32, 2))),
    "kernels": dict(
        attn=(dict(b=2, t=32, h=2, d=64), dict(b=2, t=32, h=2, d=16)),
        attn_window=dict(b=2, t=40, h=4, hkv=2, d=16, window=12),
        moe=dict(n=64, d=16, f=8),
        moe_layers=(dict(name="tiny", n=64, d=128, e=8, held=2, f=64,
                         top_k=3, activation="relu", scoring="softmax"),
                    dict(name="tiny ungated", n=64, d=128, router_d=64, e=16,
                         held=4, f=128, top_k=3, gated=False,
                         activation="relu2", scoring="sigmoid")),
        dead_rows=(dict(name="tiny", n=64, d=256, e=8, held=2, f=128,
                        top_k=3, activation="silu", scoring="softmax"),
                   dict(name="tiny ungated", n=64, d=256, router_d=64, e=16,
                        held=4, f=128, top_k=5, gated=False,
                        activation="relu2", scoring="sigmoid"),
                   dict(name="tiny whole", n=64, d=256, e=4, held=4, f=128,
                        top_k=2, activation="relu", scoring="softmax")),
        routing_sites=(dict(name="tiny", n=64, e=16, held=4, top_k=5),
                       dict(name="tiny whole", n=64, e=8, held=8, top_k=2)),
        gated_delta=dict(b=2, t=40, hk=2, hv=4, d=16),
        causal_conv=dict(b=2, t=64, c=256, width=4),
        xent=dict(n=32, v=64),
        ln=(dict(b=2, t=16, d=32), dict(b=3, t=7, d=32)),
        lstm=(dict(b=5, t=6, d=8, reverse=True),),
        lstmp=dict(b=5, t=6, d=8, p=4),
        seq_softmax=dict(b=6, t=10),
        seq_pool=dict(b=6, t=9, f=4)),
    "looped": dict(hidden_size=32, num_attention_heads=4,
                   intermediate_size=48, vocab_size=64, num_hidden_layers=2,
                   t=32, tol=5e-2),
    "lfm2": dict(t=64, d=256, width=3, experts=8, held=4, f=128, top_k=2),
    "embedding": (("a row of 8 KiB", 96, 200, 2048),
                  ("a row of 1 KiB", 96, 200, 256)),
    "latent": dict(t=64, h=2, d=128, dr=64, streams=4, c=128, iters=20,
                   tol=3e-2),
    "selective_scan": dict(b=2, t=72, c=1024, n=4, tol=2e-3),
    "rope": dict(cases=(("q", 2, 40, 4, 128, 1e6), ("k", 1, 37, 2, 128, 1e4),
                        ("a head of 256", 1, 24, 2, 256, 1e6)),
                 sweep=(1 << 15,)),
    "ssd": dict(b=2, t=72, h=4, p=64, n=16, tol=3e-2, segment=8,
                sweep=((16, 2),)),
    "kda": dict(b=2, t=72, h=2, d=16, tol=2e-2, exact_tol=5e-2),
    "rms_head": dict(cases=(("q", 2, 40, 4, 128, False),
                            ("k", 1, 37, 2, 128, False),
                            ("a head of 256", 1, 24, 2, 256, True)),
                     sweep=(1 << 16,)),
    "differential": dict(b=1, t=64, pairs=4, kv_pairs=2, hd=16, window=16,
                         tol=2e-2),
    "latent_unequal": dict(b=2, t=64, h=2, d=192, dr=64, dv=256,
                           blocks={"whole": (32, 32), "two_part": (32, 32)},
                           tol=3e-2),
    "head": dict(shapes=((48, 32, 200),), ragged=(40, 200), rows=(16, 32)),
    "expert_gmm": dict(shapes=(("a share held", 64, 128, 8, 2, 128, 3),
                               ("every expert held", 32, 128, 4, 4, 256, 2)),
                       block_m=(16, 32), tol=2e-2),
    "barrier": dict(steps=5, rounds=3, tol=0.75),
    "dp_loss_rtol": 2e-2,
}
# tiny runs pin the flash crossover to their long T and turn the kernels
# on, so the CPU rehearsal walks the same dispatch (interpreted)
TINY_ENV = {"PADDLE_TPU_PALLAS": "1", "FLAGS_flash_min_seq": "32"}

# written tolerances for phase C, as max|a-b| / (max|b| + 1e-6). f32
# elementwise kernels differ from XLA only by exp/log/rsqrt rounding; the
# LSTM kernels and flash multiply on the MXU, where Mosaic's and XLA's
# f32/bf16 matmul passes differ and T recurrent steps compound it.
TOL = {"attn": 3e-2, "xent": 1e-4, "ln": 1e-4, "lstm": 3e-2, "seq": 1e-4}


class Smoke(object):
    """What the phases share: the device, the sizes, the compile
    counters, and phase A's program for D and E."""

    def __init__(self, device, n_devices, cfg, counts):
        self.device = device
        self.n_devices = n_devices
        self.cfg = cfg
        self.counts = counts        # XLA compile requests / cache hits
        self.tag = "[platform=%s kind=%s]" % (device.platform,
                                              device.device_kind)
        self.resnet = None          # set by phase A

    def say(self, msg):
        print("%s %s" % (self.tag, msg), flush=True)


def _check_training(name, losses):
    if not all(np.isfinite(losses)):
        raise AssertionError("%s: non-finite loss in %r" % (name, losses))
    if not losses[-1] < losses[0]:
        raise AssertionError("%s: loss did not fall: %r" % (name, losses))


def _train_steps(smoke, name, exe, program, feed, loss, steps):
    """`steps` single steps on one fixed batch; returns the losses. Checks
    that nothing compiles after step 1."""
    losses, secs = [], []
    for i in range(steps):
        t0 = time.perf_counter()
        out, = exe.run(program, feed=feed, fetch_list=[loss])
        secs.append(time.perf_counter() - t0)
        losses.append(float(np.ravel(out)[0]))
        if i == 0:
            cached = len(exe._cache)
            requests = smoke.counts["requests"]
    if len(exe._cache) != cached:
        raise AssertionError("%s: executor jit cache grew after step 1 "
                             "(%d -> %d)" % (name, cached, len(exe._cache)))
    if smoke.counts["requests"] != requests:
        raise AssertionError("%s: %d XLA compile request(s) after step 1"
                             % (name, smoke.counts["requests"] - requests))
    smoke.say("%s: step 1 %.2fs (compile + run), steps 2-%d median %.4fs; "
              "loss %.4f -> %.4f" % (name, secs[0], steps,
                                     statistics.median(secs[1:]),
                                     losses[0], losses[-1]))
    _check_training(name, losses)
    return losses


def _check_state_on_device(smoke, name, scope):
    import jax
    n = 0
    for var in scope.names():
        v = scope.get(var)
        if isinstance(v, jax.Array):
            n += 1
            if any(d.platform != smoke.device.platform
                   for d in v.devices()):
                raise AssertionError(
                    "%s: persistable %r lives on %s, not on a %s device"
                    % (name, var, v.devices(), smoke.device.platform))
    if n == 0:
        raise AssertionError("%s: no device-resident persistable" % name)
    smoke.say("%s: %d persistables, all on %s" % (name, n,
                                                 smoke.device.platform))


# --------------------------------------------------------------- phase A --
def phase_a(smoke):
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.models.image_classification import build_train

    c = smoke.cfg["resnet"]
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        _, _, avg_cost, _ = build_train(
            model=c["model"], class_dim=c["class_dim"],
            image_shape=(3, c["hw"], c["hw"]), use_bf16=True)
    rng = np.random.RandomState(0)
    host_feed = {
        "image": rng.rand(c["batch"], 3, c["hw"], c["hw"]).astype("f"),
        "label": rng.randint(0, c["class_dim"],
                             (c["batch"], 1)).astype("int32")}
    feed = {k: jax.device_put(v, smoke.device) for k, v in host_feed.items()}
    exe = fluid.Executor(fluid.TPUPlace())
    scope = fluid.Scope()
    name = "%s b%d bf16" % (c["model"], c["batch"])
    with fluid.scope_guard(scope):
        t0 = time.perf_counter()
        exe.run(startup)
        smoke.say("%s: startup %.2fs" % (name, time.perf_counter() - t0))
        losses = _train_steps(smoke, name, exe, main, feed, avg_cost,
                              c["steps"])
        _check_state_on_device(smoke, name, scope)
        t0 = time.perf_counter()
        block, = exe.run(main, feed=feed, fetch_list=[avg_cost],
                         steps=c["scan_steps"])
        smoke.say("%s: run(steps=%d) %.2fs (compile + run), losses %s"
                  % (name, c["scan_steps"], time.perf_counter() - t0,
                     np.round(np.ravel(block), 4).tolist()))
        if np.ravel(block).shape != (c["scan_steps"],) \
                or not np.isfinite(block).all():
            raise AssertionError("%s: run(steps=%d) fetched %r"
                                 % (name, c["scan_steps"], block))
    smoke.resnet = dict(main=main, startup=startup, loss=avg_cost, exe=exe,
                        scope=scope, feed=feed, host_feed=host_feed,
                        first_loss=losses[0], name=name)


# --------------------------------------------------------------- phase B --
def phase_b(smoke):
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer
    from paddle_tpu.ops.kernel_config import flash_at

    c = smoke.cfg["transformer"]
    for seq, batch in c["shapes"]:
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 1
        main.enable_mixed_precision()
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            _, avg_cost, _ = transformer.build_train(
                src_vocab_size=c["vocab"], trg_vocab_size=c["vocab"],
                max_length=seq, n_layer=c["n_layer"], n_head=c["n_head"],
                d_key=c["d_model"] // c["n_head"],
                d_value=c["d_model"] // c["n_head"], d_model=c["d_model"],
                d_inner_hid=c["d_inner"], label_smooth_eps=0.1,
                use_fused_attention=True)
        rng = np.random.RandomState(0)
        srcs = [rng.randint(3, c["vocab"], seq).tolist()
                for _ in range(batch)]
        feed = {k: jax.device_put(v, smoke.device) for k, v in
                transformer.prepare_batch(srcs, srcs, seq, c["n_head"],
                                          fused=True).items()}
        name = "transformer L%d d%d T%d b%d bf16 (%s attention)" % (
            c["n_layer"], c["d_model"], seq, batch,
            "flash" if flash_at(seq) else "dense")
        exe = fluid.Executor(fluid.TPUPlace())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            t0 = time.perf_counter()
            exe.run(startup)
            smoke.say("%s: startup %.2fs" % (name, time.perf_counter() - t0))
            _train_steps(smoke, name, exe, main, feed, avg_cost, c["steps"])
            _check_state_on_device(smoke, name, scope)
    if [flash_at(seq) for seq, _ in c["shapes"]] != [False, True]:
        raise AssertionError("the two shapes must sit on either side of "
                             "the flash crossover")


# --------------------------------------------------------------- phase C --
def _pallas_calls(program, feed, fetch_names, scope, device):
    """The `interpret` flag of every pallas_call in the function the
    Executor would compile for this dispatch."""
    import jax
    from paddle_tpu.core import lowering
    from paddle_tpu.core.executor import convert_feeds

    feed_arrays = convert_feeds(program, feed)
    feed_names = sorted(feed_arrays)
    rw, ro, out = lowering.analyze_state(program, feed_names, fetch_names)
    fn = lowering.build_program_fn(program, feed_names, fetch_names, rw, ro,
                                   out, collect_errors=True)
    with jax.default_device(device):
        closed = jax.make_jaxpr(fn)(
            [feed_arrays[n] for n in feed_names],
            [scope.get(n) for n in rw], [scope.get(n) for n in ro],
            np.uint32(0))
    return _interpret_flags(closed.jaxpr)


def _interpret_flags(jaxpr):
    """The `interpret` flag of every pallas_call under `jaxpr`."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(bool(eqn.params["interpret"]))
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else [val]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found.extend(_interpret_flags(sub))
    return found


def _one_op_program(build):
    """(main, startup, fetch names) of the one-op Program `build` makes."""
    import paddle_tpu as fluid

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        fetches = build(main)
    return main, startup, [f if isinstance(f, str) else f.name
                           for f in fetches]


def _normalized_errors(names, got, want):
    """{fetch: max|a-b| / (max|b| + 1e-6)}; shapes equal, values finite."""
    errs = {}
    for n, a, b in zip(names, got, want):
        a = np.asarray(a, np.float64)
        b = np.asarray(b, np.float64)
        if a.shape != b.shape or not np.isfinite(a).all():
            raise AssertionError("%s: shape %s vs %s, finite=%s"
                                 % (n, a.shape, b.shape,
                                    bool(np.isfinite(a).all())))
        errs[n] = float(np.abs(a - b).max() / (np.abs(b).max() + 1e-6))
    return errs


def _kernel_case(smoke, label, op, build, feed, tol):
    """One family at one shape: build the one-op Program, run it with the
    kernel on and off, compare every fetch, inspect the trace."""
    import paddle_tpu as fluid

    main, startup, names = _one_op_program(build)
    exe = fluid.Executor(fluid.TPUPlace())
    scope = fluid.Scope()
    saved = os.environ.get("PADDLE_TPU_PALLAS")
    try:
        with fluid.scope_guard(scope):
            exe.run(startup)
            os.environ["PADDLE_TPU_PALLAS"] = op
            calls = _pallas_calls(main, feed, names, scope, smoke.device)
            t0 = time.perf_counter()
            got = exe.run(main, feed=feed, fetch_list=names)
            secs = time.perf_counter() - t0
            os.environ["PADDLE_TPU_PALLAS"] = "0"
            if _pallas_calls(main, feed, names, scope, smoke.device):
                raise AssertionError("PADDLE_TPU_PALLAS=0 still traces a "
                                     "pallas_call")
            want = exe.run(main, feed=feed, fetch_list=names)
    finally:
        if saved is None:
            os.environ.pop("PADDLE_TPU_PALLAS", None)
        else:
            os.environ["PADDLE_TPU_PALLAS"] = saved
    if not calls:
        raise AssertionError("the kernel was not dispatched (no "
                             "pallas_call in the trace)")
    interpreted = smoke.device.platform != "tpu"
    if calls != [interpreted] * len(calls):
        raise AssertionError("pallas_call interpret flags %r on %s"
                             % (calls, smoke.device.platform))
    errs = _normalized_errors(names, got, want)
    worst = max(errs, key=errs.get)
    smoke.say("kernel %s: %d pallas_call(s), %s; first run %.2fs; max "
              "normalized error %.2e (%s) <= %.0e"
              % (label, len(calls),
                 "interpreted" if interpreted else "Mosaic", secs,
                 errs[worst], worst, tol))
    if errs[worst] > tol:
        raise AssertionError("%s disagrees with the XLA path: %r (tol %g)"
                             % (label, errs, tol))


def _weighted_loss(fluid, out, g):
    """sum(out * g): a random cotangent, so the backward is not trivial."""
    loss = fluid.layers.reduce_sum(out * g)
    fluid.append_backward(loss)
    return loss


def _param_grads(main):
    return [p.name + "@GRAD" for p in main.global_block().all_parameters()]


def _ragged(rng, b, t, feat, scale):
    """b sequences of ragged length <= t (one full, one of length 1)."""
    lens = rng.randint(1, t + 1, size=b)
    lens[0], lens[-1] = t, 1
    return [(rng.randn(n, feat) * scale).astype("float32") for n in lens]


def _kernel_cases(cfg):
    """(label, PADDLE_TPU_PALLAS op name, build(main) -> fetches, feed,
    tolerance) for every family."""
    import paddle_tpu as fluid
    from paddle_tpu.core.lod import LoDTensor

    layers = fluid.layers
    rng = np.random.RandomState(7)
    cases = []

    # flash attention: bf16 q/k/v as the AMP transformer feeds it (the
    # fused_attention op is an AMP bf16 op), decoder-style (causal +
    # key lengths) and encoder-style (key lengths only)
    for c, causal in itertools.product(cfg["attn"], (True, False)):
        shape = (c["b"], c["t"], c["h"], c["d"])

        def build(main, causal=causal, shape=shape):
            main.enable_mixed_precision()
            q, k, v, g = (layers.data(name=n, shape=list(shape[1:]),
                                      dtype="float32") for n in "qkvg")
            for x in (q, k, v):
                x.stop_gradient = False
            kv_len = layers.data(name="kv_len", shape=[1], dtype="int32")
            out = layers.fused_attention(q, k, v, causal=causal,
                                         kv_len=kv_len)
            _weighted_loss(fluid, out, g)
            return [out, "q@GRAD", "k@GRAD", "v@GRAD"]
        feed = {n: (rng.randn(*shape) * 0.5).astype("f") for n in "qkvg"}
        feed["kv_len"] = rng.randint(shape[1] // 2, shape[1] + 1,
                                     (shape[0], 1)).astype("int32")
        cases.append(("flash_attention %s bf16 %s"
                      % (list(shape), "causal" if causal else "padded"),
                      "attn", build, feed, TOL["attn"]))

    # the same kernels with a sliding window and grouped queries (K and V
    # with fewer heads): forward and the three gradients, dK and dV summed
    # over each group. No key lengths: a query whose whole window lies past
    # them has no row to compare (the kernel gives zeros, XLA an average)
    c = cfg["attn_window"]

    def build(main, c=c):
        main.enable_mixed_precision()
        q, g = (layers.data(name=n, shape=[c["t"], c["h"], c["d"]],
                            dtype="float32") for n in "qg")
        k, v = (layers.data(name=n, shape=[c["t"], c["hkv"], c["d"]],
                            dtype="float32") for n in "kv")
        for x in (q, k, v):
            x.stop_gradient = False
        out = layers.fused_attention(q, k, v, causal=True,
                                     window=c["window"])
        _weighted_loss(fluid, out, g)
        return [out, "q@GRAD", "k@GRAD", "v@GRAD"]
    feed = {n: (rng.randn(c["b"], c["t"], c["h" if n in "qg" else "hkv"],
                          c["d"]) * 0.5).astype("f") for n in "qkvg"}
    cases.append(("flash_attention [%d, %d, %d on %d, %d] bf16 causal "
                  "window %d" % (c["b"], c["t"], c["h"], c["hkv"], c["d"],
                                 c["window"]),
                  "attn", build, feed, TOL["attn"]))

    # softmax_xent: f32 logits (AMP forces the loss ops to f32)
    c = cfg["xent"]

    def build(main, c=c):
        logits = layers.data(name="logits", shape=[c["v"]],
                             dtype="float32")
        logits.stop_gradient = False
        label = layers.data(name="label", shape=[1], dtype="int64")
        g = layers.data(name="g", shape=[1], dtype="float32")
        loss = layers.softmax_with_cross_entropy(logits=logits, label=label)
        _weighted_loss(fluid, loss, g)
        return [loss, "logits@GRAD"]
    cases.append(("softmax_xent [%d, %d] f32" % (c["n"], c["v"]), "xent",
                  build,
                  {"logits": (rng.randn(c["n"], c["v"]) * 2).astype("f"),
                   "label": rng.randint(0, c["v"],
                                        (c["n"], 1)).astype("int32"),
                   "g": rng.rand(c["n"], 1).astype("f")}, TOL["xent"]))

    # layer_norm: f32 rows (the residual sum it follows is f32 under AMP)
    for c in cfg["ln"]:
        def build(main, c=c):
            x = layers.data(name="x", shape=[c["t"], c["d"]],
                            dtype="float32")
            x.stop_gradient = False
            g = layers.data(name="g", shape=[c["t"], c["d"]],
                            dtype="float32")
            y = layers.layer_norm(x, begin_norm_axis=2)
            _weighted_loss(fluid, y, g)
            return [y, "x@GRAD"] + _param_grads(main)
        shape = (c["b"], c["t"], c["d"])
        cases.append(("layer_norm %s f32" % list(shape), "ln", build,
                      {"x": (rng.randn(*shape) * 2 + 0.5).astype("f"),
                       "g": rng.randn(*shape).astype("f")}, TOL["ln"]))

    # fused LSTM / LSTMP: f32, no peepholes (the kernel's only config)
    def lstm_case(c, proj):
        d = c["d"]

        def build(main):
            x = layers.data(name="x", shape=[4 * d], dtype="float32",
                            lod_level=1)
            x.stop_gradient = False
            if proj:
                hidden, _ = layers.dynamic_lstmp(
                    input=x, size=4 * d, proj_size=c["p"],
                    use_peepholes=False)
            else:
                hidden, _ = layers.dynamic_lstm(
                    input=x, size=4 * d, use_peepholes=False,
                    is_reverse=c["reverse"])
            fluid.append_backward(layers.mean(layers.square(hidden)))
            return [hidden, "x@GRAD"] + _param_grads(main)
        label = "fused_%s B%d T%d D%d%s f32" % (
            "lstmp" if proj else "lstm", c["b"], c["t"], d,
            " P%d" % c["p"] if proj
            else (" reverse" if c["reverse"] else ""))
        feed = {"x": LoDTensor.from_sequences(
            _ragged(rng, c["b"], c["t"], 4 * d, 0.4))}
        return (label, "lstm", build, feed, TOL["lstm"])
    cases.extend(lstm_case(c, False) for c in cfg["lstm"])
    cases.append(lstm_case(cfg["lstmp"], True))

    # masked softmax / pool over ragged sequences, f32
    def seq_case(label, feat, build_out, c):
        def build(main):
            x = layers.data(name="x", shape=[feat], dtype="float32",
                            lod_level=1)
            x.stop_gradient = False
            out = build_out(x)
            fluid.append_backward(layers.mean(layers.square(out)))
            return [out, "x@GRAD"]
        feed = {"x": LoDTensor.from_sequences(
            _ragged(rng, c["b"], c["t"], feat, 1.5))}
        return (label, "seq", build, feed, TOL["seq"])
    c = cfg["seq_softmax"]
    cases.append(seq_case(
        "masked_softmax [%d, %d] f32" % (c["b"], c["t"]), 1,
        lambda x: layers.sequence_softmax(input=x), c))
    c = cfg["seq_pool"]
    for ptype in ("sqrt", "average"):
        cases.append(seq_case(
            "masked_pool %s [%d, %d, %d] f32" % (ptype, c["b"], c["t"],
                                                 c["f"]), c["f"],
            lambda x, ptype=ptype: layers.sequence_pool(input=x,
                                                        pool_type=ptype),
            c))
    return cases


def _cpu_place_case(smoke, build, feed, tol):
    """Executor(CPUPlace()) on this host dispatches to the CPU: whatever
    the default backend is, its trace must hold no Mosaic call, and its
    result must agree with the TPUPlace executor's."""
    import jax
    import paddle_tpu as fluid

    main, startup, names = _one_op_program(build)
    outs = []
    for place in (fluid.CPUPlace(), fluid.TPUPlace()):
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(place)
            exe.run(startup)
            if not outs:
                calls = _pallas_calls(main, feed, names, scope,
                                      jax.devices("cpu")[0])
            outs.append(exe.run(main, feed=feed, fetch_list=names))
    if not all(calls):
        raise AssertionError("Executor(CPUPlace()) traced a Mosaic "
                             "pallas_call: interpret flags %r" % calls)
    err = max(_normalized_errors(names, *outs).values())
    smoke.say("Executor(CPUPlace()) next to Executor(TPUPlace()): %d "
              "pallas_call(s) in the CPU trace, none Mosaic; max "
              "normalized error %.2e <= %.0e" % (len(calls), err, tol))
    if err > tol:
        raise AssertionError("CPUPlace and TPUPlace disagree: %g" % err)


def _held_experts_case(smoke, c, tol):
    """parallel/moe.py routed_ffn told that it holds a share of 16 experts,
    on this device, against the plain reference: the partial sum and its
    gradients. On a TPU `ragged_dot` leaves the rows past the groups' sum
    unwritten (PERF.md, PR 31), and the sorted rows are gathered only up to
    that sum (PR 32); whatever lies past it must reach nothing.

    Holding experts 8-11, twice: SiLU experts at the device's default matmul
    precision (one bf16 pass on a TPU, hence the flash tolerance), and ReLU
    experts with both sides at "highest": a ReLU gate's derivative is a
    step, so at one bf16 pass the pre-activations within 0.2 % of zero
    change side and the gradients that pass through the gate (dx, dw_gate)
    leave the float32 ones by a fifth (my chip run, PR 31), which says
    nothing about the rows. Then the two extremes of imbalance, holding
    experts 4-11 with a router steered by a constant feature of its input:
    every assignment held (the loops run every tile) and none (no trip; the
    share and its gradients are zero)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import causal_lm_reference as reference
    from paddle_tpu.parallel import moe

    rng = np.random.RandomState(11)
    n, d, f, e = c["n"], c["d"], c["f"], 16
    x, a, g = (jnp.asarray(rng.randn(n, d), jnp.float32) for _ in range(3))
    router = jnp.asarray(rng.randn(d, e), jnp.float32)
    wg, wu = (jnp.asarray(rng.randn(e, d, f) * d ** -0.5, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.randn(e, f, d) * f ** -0.5, jnp.float32)
    # the other features small, so that no held expert's probability
    # underflows to the absent ones' zero and ties with them
    steered_a = (a * d ** -0.5).at[:, 0].set(10.0)

    for activation, precision, limit, first, held, steer in (
            ("silu", None, tol, 8, 4, None),
            ("relu", "highest", 1e-4, 8, 4, None),
            ("relu", "highest", 1e-4, 4, 8, 5.0),
            ("relu", "highest", 1e-4, 4, 8, -5.0)):
        conf = {"num_experts": e, "num_experts_per_tok": 6,
                "norm_topk_prob": True, "hidden_act": activation}
        sl = slice(first, first + held)
        route, route_x = router, a
        if steer is not None:
            route = router.at[0].set(0.0).at[0, sl].set(steer)
            route_x = steered_a

        def program(x, wg, wu, wd):
            return moe.routed_ffn(x, route, wg, wu, wd, top_k=6,
                                  norm_topk_prob=True, router_x=route_x,
                                  activation=activation,
                                  first_expert=first)[::3]

        def plain(x, wg, wu, wd):
            return reference.routed_experts(x, route, wg, wu, wd, conf,
                                            router_x=route_x,
                                            first_expert=first)[0]

        args = (x, wg[sl], wu[sl], wd[sl])
        with jax.default_device(smoke.device):
            with jax.default_matmul_precision(precision or "default"):
                got, vjp, load = jax.vjp(jax.jit(program), *args,
                                         has_aux=True)
                got = (got,) + vjp(g)
            with jax.default_matmul_precision("highest"):
                want, vjp = jax.vjp(jax.jit(plain), *args)
                want = (want,) + vjp(g)
        rows = int(np.asarray(load)[sl].sum())
        if steer is not None and rows != (6 * n if steer > 0 else 0):
            raise AssertionError("the steered router left %d of %d "
                                 "assignments on the held experts"
                                 % (rows, 6 * n))
        errs = _normalized_errors(
            ("out", "dx", "dw_gate", "dw_up", "dw_down"), got, want)
        worst = max(errs, key=errs.get)
        smoke.say("routed_ffn holding experts %d-%d of %d (%d of %d "
                  "assignments), [%d, %d] x [%d], %s at precision %s: max "
                  "normalized error %.2e (%s) <= %.0e"
                  % (first, first + held - 1, e, rows, 6 * n, n, d, f,
                     activation, precision or "default", errs[worst], worst,
                     limit))
        if errs[worst] > limit:
            raise AssertionError(
                "the held experts' share disagrees with the reference: %r "
                "(tol %g)" % (errs, limit))


def _slot_sum_of_pr_32(rows, rank, total, slots, gate=None):
    """A token's sum as PR 32 left it where a share is held: the gather of
    all top_k * N rows by `rank`, the unheld ones selected away, a float32
    sum slot by slot. Kept here so that the form PR 40 replaced can be timed
    beside the one in parallel/moe.py."""
    import jax.numpy as jnp
    by_slot = rows[rank].reshape(slots, -1, rows.shape[1])
    held = (rank < total).reshape(slots, -1, 1)
    acc = 0.0
    for j in range(slots):
        term = jnp.where(held[j], by_slot[j], 0).astype(jnp.float32)
        acc = acc + (term if gate is None else term * gate[j][:, None])
    return acc.astype(rows.dtype)


def _held_layer_times(smoke, c, expert_bias=None):
    """One expert layer of which a share is held, alone at a cell's sizes,
    bf16 experts as under AMP: the token-side sum alone, weighted
    (`_combine` forward) and plain (the backward of the rows' dispatch), in
    PR 32's form (a gather of all the numbered rows) and in parallel/moe.py's
    (`_token_sum`: the held rows only), the two compared; then the layer
    forward and forward + backward. The assignments are numbered as
    `moe.numbered_by` says for the shapes (by held expert where the share is
    narrower than top_k); `gated` False: experts of two matrices, relu2;
    `router_d`: the router reads a tensor of that width. Times for the next
    reader (no metric); PERF.md section 6, PR 40, quotes them.
    Sub-millisecond times taken this way hold the host's dispatch."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel import moe

    n, d, e, held, f, k = (c[key] for key in ("n", "d", "e", "held", "f",
                                              "top_k"))
    by_expert = moe.numbered_by(e, held, k) == "expert"
    slots = held if by_expert else k
    rng = np.random.RandomState(29)
    x, g = (jnp.asarray(rng.randn(n, d), jnp.bfloat16) for _ in range(2))
    router_x = jnp.asarray(rng.randn(n, c["router_d"]), jnp.bfloat16) \
        if "router_d" in c else None
    router = jnp.asarray(rng.randn(c.get("router_d", d), e) * 0.02,
                         jnp.float32)
    wg, wu = (jnp.asarray(rng.randn(held, d, f) * 0.02, jnp.float32)
              for _ in range(2))
    if not c.get("gated", True):
        wg = None
    wd = jnp.asarray(rng.randn(held, f, d) * 0.02, jnp.float32)
    rows = jnp.asarray(rng.randn(slots * n, d), jnp.bfloat16)

    def integers(x, router):
        """gate [slots, N], rank [A] and the held rows, as routed_ffn makes
        them for experts 0 .. held - 1."""
        logits = jnp.dot((x if router_x is None else router_x)
                         .astype(jnp.float32), router,
                         precision=jax.lax.Precision.HIGHEST)
        _, _, gate, expert = moe._route(logits, k, True, c["scoring"],
                                        expert_bias, 1.0)
        expert, gate = expert.T, gate.T
        if by_expert:
            sizes = jnp.sum(expert.reshape(-1)[:, None] == jnp.arange(held),
                            axis=0, dtype=jnp.int32)
            gate, _, rank = moe._by_held_expert(expert, gate, sizes)
            return gate, rank, sizes.sum()
        expert = expert.reshape(-1)
        order = jnp.argsort(jnp.where(expert < held, expert, held),
                            stable=True)
        return gate, jnp.argsort(order), jnp.sum(expert < held)

    def layer(x, router, wg, wu, wd):
        return moe.routed_ffn(
            x, router, wg, wu, wd, k, True, expert_dtype=jnp.bfloat16,
            router_x=router_x, activation=c["activation"],
            scoring=c["scoring"], expert_bias=expert_bias)[0]

    def trained(x, router, wg, wu, wd, g):
        return jax.vjp(layer, x, router, wg, wu, wd)[1](g)

    def new(rows, rank, total, gate=None):
        return moe._token_sum((rows,), rank,
                              moe._token_places(rank, total, slots), gate,
                              tile=moe.SUM_TILE)

    def old(rows, rank, total, gate=None):
        return _slot_sum_of_pr_32(rows, rank, total, slots, gate)

    with jax.default_device(smoke.device):
        gate, rank, total = jax.jit(integers)(x, router)
        times, worst = {}, 0.0
        for form, fn in (("old", old), ("new", new)):
            for name, args in (("weighted", (rows, rank, total, gate)),
                               ("plain", (rows, rank, total))):
                times[form, name] = _in_flight_ms(jax.jit(fn), args)
        for args in ((rows, rank, total, gate), (rows, rank, total)):
            want = jax.jit(old)(*args).astype(jnp.float32)
            got = jax.jit(new)(*args).astype(jnp.float32)
            worst = max(worst, float(jnp.abs(got - want).max()
                                     / jnp.abs(want).max()))
        if worst > 2.0 ** -7:       # both round a float32 sum to bf16 once
            raise AssertionError(
                "the token-side sum over the held rows is %.2e off the "
                "gather of all rows" % worst)
        args = (x, router, wg, wu, wd)
        smoke.say(
            "expert layer at %s's sizes, %d tokens of %d, top-%d of %d, %d "
            "held of width %d, %d of %d assignments held, %d numbered: a "
            "token's sum "
            "alone, weighted / plain, as a gather of all rows (PR 32) %.3f "
            "/ %.3f ms, over the held rows (%.2e apart at most) %.3f / %.3f "
            "ms; the layer forward %.3f ms, forward + backward %.3f ms"
            % (c["name"], n, d, k, e, held, f, total, k * n, slots * n,
               times["old", "weighted"], times["old", "plain"], worst,
               times["new", "weighted"], times["new", "plain"],
               _in_flight_ms(jax.jit(layer), args),
               _in_flight_ms(jax.jit(trained), args + (g,))))


def _routing_site_times(smoke, c):
    """The scalars `routed_ffn` moves between the router's top-k and the
    rows' passes, each site alone at a cell's shape in the form the program
    had up to PR 62 (XLA's gather and scatter of 32-bit scalars; the old
    forms live here and nowhere in the program) and in the one it has (a
    compare, `moe._chosen`; sorts, `moe._sorted_by`), the two compared to
    the bit: (a) the chosen scores out of [N, E] and their gradient into it,
    under a bias (`take_along_axis`) and without (`top_k`'s own values);
    (b) `rank`, the inverse of `order`; (c) the weights by sorted row; (d)
    their gradients back to the assignments' numbering. A is the
    assignments as `moe.numbered_by` numbers them. In flight, so the
    numbers order the forms; a cell's trace by scope is the number."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel import moe

    n, e, held, k = (c[key] for key in ("n", "e", "held", "top_k"))
    slots = held if moe.numbered_by(e, held, k) == "expert" else k
    rng = np.random.RandomState(31)
    probs = jax.nn.sigmoid(jnp.asarray(rng.randn(n, e), jnp.float32))
    d_gate = jnp.asarray(rng.randn(n, k), jnp.float32)
    _, expert = jax.lax.top_k(
        probs + jnp.asarray(rng.randn(e) * 0.1, jnp.float32), k)
    order = jnp.asarray(rng.permutation(slots * n), jnp.int32)
    rank = jnp.argsort(order).astype(jnp.int32)
    scalars = jnp.asarray(rng.randn(slots * n), jnp.float32)
    iota = jnp.arange(slots * n, dtype=jnp.int32)

    def with_gradient(read):
        def run(probs, expert, d_gate):
            out, vjp = jax.vjp(lambda p: read(p, expert), probs)
            return out, vjp(d_gate)[0]
        return run

    def own_values(probs, expert):          # no bias: what top_k returns
        return jax.lax.top_k(probs, k)[0]

    def chosen_again(probs, expert):
        return moe._chosen(probs, jax.lax.top_k(probs, k)[1])

    def gathered(probs, expert):
        return jnp.take_along_axis(probs, expert, axis=-1)

    sites = (
        ("(a) the chosen scores [%d, %d] of [.., %d]" % (n, k, e),
         gathered, moe._chosen, (probs, expert)),
        ("(a) and their gradient", with_gradient(gathered),
         with_gradient(moe._chosen), (probs, expert, d_gate)),
        ("(a) with no bias, the top-k and its values",
         own_values, chosen_again, (probs, expert)),
        ("(a) with no bias, and their gradient", with_gradient(own_values),
         with_gradient(chosen_again), (probs, expert, d_gate)),
        ("(b) rank of %d" % (slots * n),
         lambda order: jnp.zeros_like(order).at[order].set(iota),
         lambda order: moe._sorted_by(order, iota)[0], (order,)),
        ("(c) the weights by sorted row",
         lambda w, order, rank: w[order],
         lambda w, order, rank: moe._sorted_by(rank, w)[0],
         (scalars, order, rank)),
        ("(d) their gradients back",
         lambda w, order, rank: w[rank],
         lambda w, order, rank: moe._sorted_by(order, w)[0],
         (scalars, order, rank)))
    lines = []
    with jax.default_device(smoke.device):
        for label, old, new, args in sites:
            old, new = jax.jit(old), jax.jit(new)
            for got, want in zip(jax.tree_util.tree_leaves(new(*args)),
                                 jax.tree_util.tree_leaves(old(*args))):
                if not bool(jnp.array_equal(got, want)):
                    raise AssertionError("%s at %s's shape: the two forms "
                                         "differ" % (label, c["name"]))
            lines.append("%s %.3f -> %.3f ms" % (
                label, _in_flight_ms(old, args), _in_flight_ms(new, args)))
    smoke.say("routed_ffn's scalars at %s's shape, %d tokens top-%d of %d, "
              "%d held, through XLA's gather / scatter -> by compare / sort "
              "(equal to the bit; median of 5 x 10 calls in flight): %s"
              % (c["name"], n, k, e, held, "; ".join(lines)))


def _rows_by_dma(x3, token, total, block):
    """x3 [N, C, 128] of 32-bit words, a token's row the C x 128 of one
    leading index -> [A, C, 128]: row r is x3[token[r]] below `total`, a
    DMA a row from HBM into the output's tile in VMEM, a tile's all in
    flight at once; tiles past `total` name the last live one again and do
    nothing. The form `_held_rows` would take as a kernel (ISSUE 65 (a)),
    kept here for its one number, the ns a row a DMA gathers at: Mosaic
    slices a tiled array's rows by eights, so a row has to be a leading
    index, which a [A, D] buffer of bfloat16 rows is not (two ROWS share a
    word there); what a kernel in the program would add is that relayout
    in VMEM."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from paddle_tpu.ops.pallas_import import pl, pltpu

    def kernel(total, token_ref, x_hbm, out_ref, sem):
        live = jnp.clip(total[0] - pl.program_id(0) * block, 0, block)

        def copy(r, source):
            return pltpu.make_async_copy(x_hbm.at[source], out_ref.at[r], sem)

        @pl.when(live > 0)
        def _():
            def issue(r, carry):
                copy(r, token_ref[r]).start()
                return carry

            def wait(r, carry):
                copy(r, 0).wait()
                return carry

            lax.fori_loop(0, live, issue, 0)
            lax.fori_loop(0, live, wait, 0)

    def live_tile(i, total):
        return jnp.minimum(i, jnp.maximum(total[0] - 1, 0) // block)

    rows = token.shape[0]
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows,) + x3.shape[1:], x3.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows // block,),
            in_specs=[pl.BlockSpec((block,), lambda i, t: (live_tile(i, t),),
                                   memory_space=pltpu.SMEM),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((block,) + x3.shape[1:],
                                   lambda i, t: (live_tile(i, t), 0, 0)),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=100 << 20),
        interpret=jax.devices()[0].platform != "tpu",
        name="smoke_rows_by_dma")(total.reshape(1), token, x3)


def _dead_rows_times(smoke, c):
    """What PR 65 took off the rows of the sorted buffer that belong to no
    group, each part alone at a cell's sizes, bf16 experts as under AMP:
    (1) the gate/up matmuls and the unit as two kernels and an XLA pass over
    all A rows (the form up to PR 64, made here from the program's own
    kernels) and as ONE kernel with the unit its epilogue
    (expert_gmm.gmm_unit), the hidden rows below the groups' sum compared;
    (2) where a share is held, `_held_rows` from a buffer of zeros, from one
    nothing wrote (expert_gmm.unwritten), and the same rows gathered by a
    DMA a row (`_rows_by_dma`), with the ns a gathered row of XLA's loop and
    of the DMAs; (3) the layer forward and forward + backward with the old
    forms stood in the program's place, and as it is. In flight: the
    numbers order the forms, a cell's trace by scope is the number. Ten
    calls in flight keep ten sets of results: three where a set passes a
    quarter of a GiB (OLMoE's three [131072, 1024] are 0.75)."""
    import contextlib
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import expert_gmm
    from paddle_tpu.parallel import moe

    n, d, e, held, f, k = (c[key] for key in ("n", "d", "e", "held", "f",
                                              "top_k"))
    gated = c.get("gated", True)
    unit = moe._gated_unit(c["activation"]) if gated \
        else moe._ungated_unit(c["activation"])
    slots = held if moe.numbered_by(e, held, k) == "expert" else k
    a = slots * n
    rng = np.random.RandomState(37)
    x, g = (jnp.asarray(rng.randn(n, d), jnp.bfloat16) for _ in range(2))
    router_x = jnp.asarray(rng.randn(n, c["router_d"]), jnp.bfloat16) \
        if "router_d" in c else None
    router = jnp.asarray(rng.randn(c.get("router_d", d), e) * 0.02,
                         jnp.float32)
    wg, wu = (jnp.asarray(rng.randn(held, d, f) * 0.02, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.randn(held, f, d) * 0.02, jnp.float32)
    if not gated:
        wg = None
    rows = jnp.asarray(rng.randn(a, d), jnp.bfloat16)
    # the held experts' counts under a uniform router, as a cell's are
    chosen = np.argsort(rng.rand(n, e), axis=1)[:, :k]
    sizes = jnp.asarray(np.bincount(chosen.reshape(-1), minlength=e)[:held],
                        jnp.int32)
    total = int(sizes.sum())
    token = jnp.asarray(rng.randint(0, n, a), jnp.int32)
    timed = functools.partial(
        _in_flight_ms, calls=10 if 6 * a * max(d, f) <= 1 << 28 else 3)

    def bf16(w):
        return None if w is None else w.astype(jnp.bfloat16)

    def apart(rows, w_gate, w_up, plan, unit):
        made = [expert_gmm.gmm(rows, w, plan) for w in (w_gate, w_up)
                if w is not None]
        return made + [unit(*made)]

    def as_one(rows, wg, wu, sizes):
        return expert_gmm.gmm_unit(rows, bf16(wg), bf16(wu),
                                   expert_gmm.plan(sizes, a), unit)

    def as_three(rows, wg, wu, sizes):
        return apart(rows, bf16(wg), bf16(wu), expert_gmm.plan(sizes, a),
                     unit)

    @contextlib.contextmanager
    def up_to_pr_64():
        kept = expert_gmm.gmm_unit, expert_gmm.unwritten
        expert_gmm.gmm_unit = apart
        expert_gmm.unwritten = lambda shape, dtype, like: jnp.zeros(shape,
                                                                    dtype)
        try:
            yield
        finally:
            expert_gmm.gmm_unit, expert_gmm.unwritten = kept

    def layer(x, router, wg, wu, wd):
        return moe.routed_ffn(
            x, router, wg, wu, wd, k, True, expert_dtype=jnp.bfloat16,
            router_x=router_x, activation=c["activation"],
            scoring=c["scoring"])[0]

    def trained(x, router, wg, wu, wd, g):
        return jax.vjp(layer, x, router, wg, wu, wd)[1](g)

    with jax.default_device(smoke.device):
        if moe.matmul_route(d, f, jnp.bfloat16) != moe.KERNEL_MATMUL:
            raise AssertionError("%s's widths do not take the kernels here"
                                 % c["name"])
        said = ["%s's sizes, %d tokens of %d, top-%d of %d, %d held of "
                "width %d, %d of %d rows in a group"
                % (c["name"], n, d, k, e, held, f, total, a)]
        args = (rows, wg, wu, sizes)
        want = jax.jit(as_three)(*args)[-1][:total].astype(jnp.float32)
        got = jax.jit(as_one)(*args)[-1][:total].astype(jnp.float32)
        off = float(jnp.abs(got - want).max() / jnp.abs(want).max())
        if not off <= 2.0 ** -7:    # one rounding of the unit to bfloat16
            raise AssertionError("the unit as the kernel's epilogue is %.2e "
                                 "off the pass over the stored arrays" % off)
        said.append("(1) gate, up and the unit as two kernels and XLA's pass "
                    "%.3f ms, as one kernel %.3f ms (hidden rows %.1e apart)"
                    % (timed(jax.jit(as_three), args),
                       timed(jax.jit(as_one), args), off))
        if held < e:
            def gathered(start):
                return jax.jit(lambda x, token, total: moe._held_rows(
                    x, token, total, start(token), tile=moe.ROW_TILE))
            starts = {
                "zeros": lambda token: jnp.zeros((a, d), jnp.bfloat16),
                "unwritten": lambda token: expert_gmm.unwritten(
                    (a, d), jnp.bfloat16, token)}
            held_rows = {name: timed(gathered(start), (
                x, token, jnp.asarray(total, jnp.int32)))
                for name, start in starts.items()}
            x3 = jnp.asarray(rng.randint(0, 2 ** 31, (n, d // 256, 128)),
                             jnp.uint32)
            block = min(1024, a)
            by_dma = jax.jit(lambda x3, token, total: _rows_by_dma(
                x3, token, total, block))
            dma_args = (x3, token, jnp.asarray(total, jnp.int32))
            if not bool(jnp.array_equal(by_dma(*dma_args)[:total],
                                        x3[token[:total]])):
                raise AssertionError("the rows gathered by DMA are not "
                                     "x[token]")
            dma = timed(by_dma, dma_args)
            said.append(
                "(2) %d rows of %d bytes gathered into [%d, %d]: "
                "`_held_rows` from zeros %.3f ms, from a buffer nothing "
                "wrote %.3f ms (%.1f ns a row), a DMA a row %.3f ms (%.1f "
                "ns a row; the rows as 32-bit words [%d, 128], no relayout)"
                % (total, 2 * d, a, d, held_rows["zeros"],
                   held_rows["unwritten"],
                   1e6 * held_rows["unwritten"] / max(total, 1), dma,
                   1e6 * dma / max(total, 1), d // 256))
        args = (x, router, wg, wu, wd)
        with up_to_pr_64():
            before = (timed(jax.jit(lambda *a: layer(*a)), args),
                      timed(jax.jit(lambda *a: trained(*a)),
                                    args + (g,)))
        said.append("(3) the layer forward %.3f -> %.3f ms, forward + "
                    "backward %.3f -> %.3f ms"
                    % (before[0], timed(jax.jit(layer), args),
                       before[1], timed(jax.jit(trained),
                                                args + (g,))))
    smoke.say("routed_ffn's rows that no group reads at " + "; ".join(said))


def _gated_delta_case(smoke, c, tol):
    """ops/gated_delta_kernels.py on this device at the Qwen3-Next cell's
    shapes, bf16 operands as under AMP: the chunked forward and backward on
    the Pallas kernels, and on lax.scan, against the token-by-token
    recurrence of models/causal_lm_reference.py in float32 at "highest" and
    jax.grad of it, under two decays: a layer's at initialisation (most
    heads forget within a token or two, so what a chunk hands the next one
    hardly shows) and a slow one (a state lives some hundred tokens, across
    the chunks of 64: the carry of S and dS decides the answer). The times
    of one forward and backward of either path are printed for the next
    reader (no metric)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import causal_lm_reference as reference
    from paddle_tpu.ops import gated_delta_kernels

    rng = np.random.RandomState(13)
    b, t, hk, hv, d = c["b"], c["t"], c["hk"], c["hv"], c["d"]
    q, k = (jnp.asarray(rng.randn(b, t, hk, d), jnp.bfloat16)
            for _ in range(2))
    v, ct = (jnp.asarray(rng.randn(b, t, hv, d), jnp.bfloat16)
             for _ in range(2))
    step = np.log1p(np.exp(rng.randn(b, t, hv) + 1.0))
    beta = jax.nn.sigmoid(jnp.asarray(rng.randn(b, t, hv), jnp.float32))
    decays = (      # A in (0, 16) with dt_bias 1, and A in (0, 0.02)
        ("initialisation", rng.uniform(0, 16, (hv,))),
        ("slow", rng.uniform(0, 0.02, (hv,))))

    @jax.checkpoint
    def one_head(xs):       # [B, T, 1, ..]: a value head's T states alone
        return reference.delta_rule(*xs)

    def recurrence(q, k, v, g, beta):
        # a value head at a time, recomputed in the backward pass: jax.grad
        # of the whole recurrence keeps every token's state of every head
        # (8.6 GB at 4096 tokens, 32 heads of [128, 128])
        q, k = (jnp.repeat(reference.l2norm(x.astype(jnp.float32)),
                           hv // hk, axis=2) for x in (q, k))
        heads = tuple(jnp.moveaxis(x, 2, 0)[:, :, :, None] for x in (
            q * d ** -0.5, k, v.astype(jnp.float32), g, beta))
        return jnp.moveaxis(jax.lax.map(one_head, heads)[:, :, :, 0], 0, 2)

    def both(fn):
        def run(*a):
            out, vjp = jax.vjp(fn, *a)
            return (out,) + vjp(ct.astype(out.dtype))
        return jax.jit(run)

    names = ("out", "dq", "dk", "dv", "dg", "dbeta")
    for decay, a in decays:
        args = (q, k, v, -jnp.asarray(a * step, jnp.float32), beta)
        with jax.default_device(smoke.device):
            with jax.default_matmul_precision("highest"):
                want = both(recurrence)(*args)
            for path in ("kernel", "scan"):
                run = both(lambda *a: gated_delta_kernels.gated_delta_rule(
                    *a, path=path, operand_dtype=jnp.bfloat16))
                got = jax.block_until_ready(run(*args))
                times = []
                for _ in range(5):
                    t0 = time.perf_counter()
                    jax.block_until_ready(run(*args))
                    times.append(time.perf_counter() - t0)
                errs = _normalized_errors(names, got, want)
                worst = max(errs, key=errs.get)
                smoke.say("gated_delta_rule %s path, %s decay, q/k [%d, %d, "
                          "%d, %d] v [.., %d, %d] bf16: max normalized error "
                          "%.2e (%s) <= %.0e; forward + backward %.2f ms "
                          "(median of 5)"
                          % (path, decay, b, t, hk, d, hv, d, errs[worst],
                             worst, tol, 1e3 * statistics.median(times)))
                if errs[worst] > tol:
                    raise AssertionError(
                        "the %s path disagrees with the recurrence under "
                        "the %s decay: %r (tol %g)" % (path, decay, errs,
                                                       tol))


def _in_flight_ms(run, args, calls=10, rounds=5):
    """Median over `rounds` of the time of `calls` calls in flight, a call,
    in ms: a part of a millisecond or two is not timed through one
    dispatch. Every call's results are live until the round ends: fewer
    `calls` where they are large."""
    import jax
    jax.block_until_ready(run(*args))
    times = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        outs = [run(*args) for _ in range(calls)]
        jax.block_until_ready(outs)
        times.append((time.perf_counter() - t0) / calls)
    return 1e3 * statistics.median(times)


def _gated_delta_parts(smoke, c):
    """What XLA runs around the delta rule's kernels, each part alone at the
    Qwen3-Next cell's shapes and the table's chunk, bf16 operands as under
    AMP: (I + L)^-1 of every chunk, `_prepare` forward, and `_prepare`
    forward with the transpose jax derives for it (what a layer pays a
    step). Printed for the next reader (no metric): PERF.md section 7
    quotes them."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import gated_delta_kernels, kernel_config

    rng = np.random.RandomState(17)
    b, t, hk, hv, d = c["b"], c["t"], c["hk"], c["hv"], c["d"]
    chunk = kernel_config.DEFAULT_TILES["gdr"]["chunk"]
    n = -(-t // chunk)
    q, k = (jnp.asarray(rng.randn(b, t, hk, d), jnp.bfloat16)
            for _ in range(2))
    v = jnp.asarray(rng.randn(b, t, hv, d), jnp.bfloat16)
    g = -jnp.asarray(rng.rand(b, t, hv), jnp.float32)
    beta = jax.nn.sigmoid(jnp.asarray(rng.randn(b, t, hv), jnp.float32))
    low = jnp.asarray(np.tril(rng.randn(b, hv, n, chunk, chunk) * 0.3, -1),
                      jnp.float32)

    def prepare(*a):
        return gated_delta_kernels._prepare(*a, chunk=chunk,
                                            dt=jnp.bfloat16)

    def both(*a):
        out, vjp = jax.vjp(prepare, *a)
        return vjp(jax.tree_util.tree_map(jnp.ones_like, out))

    with jax.default_device(smoke.device):
        parts = (_in_flight_ms(jax.jit(gated_delta_kernels.unit_lower_inverse),
                               (low,)),
                 _in_flight_ms(jax.jit(prepare), (q, k, v, g, beta)),
                 _in_flight_ms(jax.jit(both), (q, k, v, g, beta)))
    smoke.say("gated_delta_rule's XLA parts, q/k [%d, %d, %d, %d] v [.., %d, "
              "%d] bf16, chunk %d: (I + L)^-1 of [%d, %d, %d, %d, %d] %.3f "
              "ms; _prepare forward %.3f ms; _prepare forward + transpose "
              "%.3f ms (median of 5 x 10 calls in flight)"
              % ((b, t, hk, d, hv, d, chunk, b, hv, n, chunk, chunk) + parts))


def phase_c(smoke):
    cases = _kernel_cases(smoke.cfg["kernels"])
    runs = [(c[0], lambda c=c: _kernel_case(smoke, *c)) for c in cases]
    runs.append(("routed_ffn with a share of the experts",
                 lambda: _held_experts_case(smoke, smoke.cfg["kernels"]["moe"],
                                            TOL["attn"])))
    for c in smoke.cfg["kernels"]["moe_layers"]:
        runs.append(("routed_ffn's passes over the held rows, %s" % c["name"],
                     lambda c=c: _held_layer_times(smoke, c)))
    for c in smoke.cfg["kernels"]["dead_rows"]:
        runs.append(("routed_ffn's rows that no group reads, %s" % c["name"],
                     lambda c=c: _dead_rows_times(smoke, c)))
    for c in smoke.cfg["kernels"]["routing_sites"]:
        runs.append(("routed_ffn's scalars by gather and by sort, %s"
                     % c["name"], lambda c=c: _routing_site_times(smoke, c)))
    runs.append(("gated_delta_rule against the recurrence",
                 lambda: _gated_delta_case(
                     smoke, smoke.cfg["kernels"]["gated_delta"],
                     TOL["attn"])))
    runs.append(("gated_delta_rule's XLA parts",
                 lambda: _gated_delta_parts(
                     smoke, smoke.cfg["kernels"]["gated_delta"])))
    _, _, ln_build, ln_feed, ln_tol = next(c for c in cases if c[1] == "ln")
    runs.append(("layer_norm on Executor(CPUPlace())",
                 lambda: _cpu_place_case(smoke, ln_build, ln_feed, ln_tol)))
    failed = []
    for label, run in runs:
        # one family's refusal must not hide the next one's: collect them
        # all, then fail the phase
        try:
            run()
        except Exception as e:  # noqa: BLE001 — reported, phase fails below
            failed.append(label)
            smoke.say("kernel %s: FAILED %s: %s"
                      % (label, type(e).__name__, e))
            traceback.print_exc()
    if failed:
        raise AssertionError("%d kernel case(s) failed: %s"
                             % (len(failed), "; ".join(failed)))


# --------------------------------------------------------------- phase D --
def phase_d(smoke):
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.parallel.mesh import batch_sharded, make_mesh

    if smoke.n_devices < 4:
        return "needs 4 devices, this host has %d" % smoke.n_devices
    if smoke.resnet is None:
        raise AssertionError("phase A left no program to run")
    r = smoke.resnet
    devices = jax.devices()[:4]
    mesh = make_mesh({"dp": 4}, devices)
    feed = {k: jax.device_put(v, batch_sharded(mesh, v.ndim))
            for k, v in r["host_feed"].items()}
    for k, v in feed.items():
        spread = {s.device for s in v.addressable_shards}
        if spread != set(devices) or any(
                s.data.shape[0] * 4 != v.shape[0]
                for s in v.addressable_shards):
            raise AssertionError("feed %r is not split over the four "
                                 "devices: %s" % (k, v.sharding))
    for sharded in (False, True):
        name = "%s dp=4%s" % (r["name"], " sharded_weight_update"
                              if sharded else "")
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            fluid.Executor(fluid.TPUPlace()).run(r["startup"])
            pexe = fluid.ParallelExecutor(
                main_program=r["main"], loss_name=r["loss"].name, mesh=mesh,
                sharded_weight_update=sharded)
            losses, secs = [], []
            for _ in range(4):
                t0 = time.perf_counter()
                out, = pexe.run([r["loss"].name], feed=feed)
                secs.append(time.perf_counter() - t0)
                losses.append(float(np.ravel(out)[0]))
            smoke.say("%s: step 1 %.2fs (compile + run), steps 2-4 median "
                      "%.4fs; loss %.4f -> %.4f"
                      % (name, secs[0], statistics.median(secs[1:]),
                         losses[0], losses[-1]))
            _check_training(name, losses)
            rtol = smoke.cfg["dp_loss_rtol"]
            if abs(losses[0] - r["first_loss"]) > rtol * abs(r["first_loss"]):
                raise AssertionError(
                    "%s: first-step loss %.5f vs one chip %.5f (rtol %g)"
                    % (name, losses[0], r["first_loss"], rtol))
            n_split = 0
            for var in scope.names():
                v = scope.get(var)
                if not isinstance(v, jax.Array) or v.ndim == 0:
                    continue
                shards = v.addressable_shards
                if {s.device for s in shards} != set(devices):
                    raise AssertionError(
                        "%s: %r is on %s, not on all four devices"
                        % (name, var, {s.device for s in shards}))
                n_split += shards[0].data.shape != v.shape
            smoke.say("%s: state on 4 distinct devices, %d variable(s) "
                      "split 1/4 per device" % (name, n_split))
            if bool(n_split) != sharded:
                raise AssertionError(
                    "%s: %d split variables" % (name, n_split))
    return None


# --------------------------------------------------------------- phase E --
def phase_e(smoke):
    import jax
    import paddle_tpu as fluid

    if smoke.resnet is None:
        raise AssertionError("phase A left no program to run")
    r, c = smoke.resnet, smoke.cfg["barrier"]

    def timed(wait):
        t0 = time.perf_counter()
        for _ in range(c["steps"]):
            out = r["exe"].run(r["main"], feed=r["feed"],
                               fetch_list=[r["loss"]], return_numpy=False)
        wait(out[0].array)
        return time.perf_counter() - t0

    block, fetch = [], []
    with fluid.scope_guard(r["scope"]):
        for _ in range(c["rounds"]):
            block.append(timed(jax.block_until_ready))
            fetch.append(timed(np.asarray))
    tb, tf = statistics.median(block), statistics.median(fetch)
    smoke.say("barrier: %d steps to block_until_ready %.4fs, to a host "
              "fetch %.4fs (medians of %d; ratio %.3f, tol %.2f)"
              % (c["steps"], tb, tf, c["rounds"], tb / tf, c["tol"]))
    if abs(tb - tf) > c["tol"] * tf:
        raise AssertionError("block_until_ready and a device->host fetch "
                             "disagree: %.4fs vs %.4fs" % (tb, tf))


# --------------------------------------------------------------- phase F --
def phase_f(smoke):
    """The causal_conv1d rule on this device, with its kernels on and off
    (PADDLE_TPU_PALLAS, as phase C switches a family): both against the
    same arithmetic on float32 copies of x and dy, where the kernels may
    be no further off than the jax.numpy passes are (dw's float32 sums run
    in another order: 1e-6 of slack). The times are printed for the next
    reader (no metric)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core import registry

    c = smoke.cfg["kernels"]["causal_conv"]
    rng = np.random.RandomState(17)
    shape = (c["b"], c["t"], c["c"])
    x, dy = (jnp.asarray(rng.randn(*shape), jnp.bfloat16) for _ in range(2))
    w = jnp.asarray(rng.randn(c["c"], c["width"]) * 0.5, jnp.float32)
    rule = registry.get("causal_conv1d").lower

    def traced_now():
        # new functions a path: jax keeps a function's trace, and the rule
        # reads PADDLE_TPU_PALLAS while it is traced
        def forward(x, w):
            return rule(None, {"X": [x], "Filter": [w]},
                        {"activation": "silu"})["Out"][0]

        def both(x, w, dy):
            y, vjp = jax.vjp(forward, x, w)
            return (y,) + vjp(dy)
        return forward, both

    names = ("y", "dx", "dw")
    saved = os.environ.get("PADDLE_TPU_PALLAS")
    found = {}
    try:
        with jax.default_device(smoke.device):
            os.environ["PADDLE_TPU_PALLAS"] = "0"
            want = jax.jit(traced_now()[1])(x.astype(jnp.float32), w,
                                            dy.astype(jnp.float32))
            for path, flag in (("kernel", "conv"), ("xla", "0")):
                os.environ["PADDLE_TPU_PALLAS"] = flag
                forward, both = traced_now()
                calls = _interpret_flags(
                    jax.make_jaxpr(both)(x, w, dy).jaxpr)
                interpreted = smoke.device.platform != "tpu"
                if calls != ([interpreted] * 2 if path == "kernel" else []):
                    raise AssertionError(
                        "causal_conv1d, PADDLE_TPU_PALLAS=%s on %s: "
                        "pallas_call interpret flags %r"
                        % (flag, smoke.device.platform, calls))
                run = jax.jit(both)
                errs = _normalized_errors(names, run(x, w, dy), want)
                found[path] = errs
                smoke.say(
                    "causal_conv1d %s path, x %s bf16, %d taps: forward "
                    "%.3f ms, forward + backward %.3f ms (median of 5 x "
                    "10 calls); off the float32 recomputation by %s"
                    % (path, list(shape), c["width"],
                       _in_flight_ms(jax.jit(forward), (x, w)),
                       _in_flight_ms(run, (x, w, dy)),
                       ", ".join("%s %.2e" % (n, errs[n]) for n in names)))
    finally:
        if saved is None:
            os.environ.pop("PADDLE_TPU_PALLAS", None)
        else:
            os.environ["PADDLE_TPU_PALLAS"] = saved
    # off a TPU the interpreter's approximate reciprocal is bf16's, and the
    # kernels' Newton step leaves the sigmoid 1.5e-5 off, not 1e-7
    slack = 1e-6 if smoke.device.platform == "tpu" else 1e-4
    worse = {n: (found["kernel"][n], found["xla"][n]) for n in names
             if found["kernel"][n] > max(1.05 * found["xla"][n], slack)}
    if worse:
        raise AssertionError(
            "the kernels are further from the float32 recomputation than "
            "the jax.numpy passes (kernel, xla): %r" % (worse,))


def phase_g(smoke):
    """The summed gradient of weights that four passes share, under bf16
    AMP as the cell runs, against the float32 reference's: a matrix of each
    layer's two branches, a norm between passes, the gate and the head.
    With recomputation and without the program's two answers may differ by
    rounding alone."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as fluid
    from paddle_tpu.models import causal_lm, causal_lm_reference as plain

    c = dict(smoke.cfg["looped"])
    t, tol = c.pop("t"), c.pop("tol")
    cfg = dict(c, rms_norm_eps=1e-6, rope_theta=1e6, total_ut_steps=4,
               sandwich_norm=True, exit_gate=True, exit_entropy_coef=0.05)
    names = ("layer_0.wq", "layer_0.w_down", "layer_1.wo", "layer_1.w_up",
             "layer_1.ffn_out_norm", "final_norm", "exit_gate.w", "head")
    rng = np.random.RandomState(23)
    tok = rng.randint(0, cfg["vocab_size"], (1, t + 1))
    feed = {"ids": tok[:, :-1], "pos": np.arange(t)[None],
            "labels": tok[:, 1:, None]}
    got = {}
    for recompute in (True, False):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 5
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            main.enable_mixed_precision()
            loss, _, _ = causal_lm.causal_lm(cfg, t, recompute=recompute)
            fluid.backward.append_backward(loss)
        params = main.global_block().all_parameters()
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.TPUPlace())
            exe.run(startup)
            weights = [np.asarray(scope.get(p.name)) for p in params]
            t0 = time.perf_counter()
            out = exe.run(main, feed=feed, fetch_list=[loss] + [
                n + "@GRAD" for n in names])
            got[recompute] = dict(zip(("loss",) + names, out))
            smoke.say("looped decoder, 4 passes of %d layers at hidden %d, "
                      "T=%d, recompute %s: loss %.5f, first run %.1fs"
                      % (cfg["num_hidden_layers"], cfg["hidden_size"], t,
                         recompute, float(out[0][0]),
                         time.perf_counter() - t0))
    (loss, _), grads = jax.jit(
        lambda w, ids, pos, labels: plain.loss_and_grads(
            cfg, w, ids, pos, labels))(
                weights, *(jnp.asarray(feed[k]) for k in ("ids", "pos",
                                                          "labels")))
    want = dict(zip((p.name for p in params), grads), loss=loss)
    against = _normalized_errors(
        names, [got[True][n] for n in names], [want[n] for n in names])
    between = _normalized_errors(
        names, [got[True][n] for n in names], [got[False][n] for n in names])
    smoke.say("gradients through four passes, recomputed, off the float32 "
              "reference by %s (loss %.5f against %.5f); recomputed against "
              "kept %s" % (
                  ", ".join("%s %.2e" % (n, against[n]) for n in names),
                  float(got[True]["loss"][0]), float(loss),
                  ", ".join("%s %.2e" % (n, between[n]) for n in names)))
    wrong = {n: e for n, e in against.items() if not e <= tol}
    wrong.update({n + " (recomputed against kept)": e
                  for n, e in between.items() if not e <= tol / 5})
    if wrong:
        raise AssertionError("gradients of shared weights off by more than "
                             "%g: %r" % (tol, wrong))


def phase_h(smoke):
    """One LFM2 short_conv mixer without its two matmuls, and one expert
    layer with its sigmoid router, each alone at the cell's sizes, bf16
    operands as under AMP. The mixer's part: [B, C, u] as the input
    projection leaves them, v = B * u, the convolution's rule, C * that;
    forward and forward + backward, with the kernels and with the jax.numpy
    passes, and the convolution's rule alone, beside the least time one
    pass over the operands takes at the chip's HBM rate. The router's part
    of `routed_ffn`: the float32 matmul, sigmoid, the biased top-k and the
    sort, against the whole layer. Both are checked against float32
    recomputations; the times are printed for the next reader (no metric):
    PERF.md section 6 quotes them."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core import registry
    from paddle_tpu.models import causal_lm_reference as plain
    from paddle_tpu.parallel import moe

    c = smoke.cfg["lfm2"]
    t, d, e, held = c["t"], c["d"], c["experts"], c["held"]
    rng = np.random.RandomState(23)
    bcu, dy = (jnp.asarray(rng.randn(1, t, n), jnp.bfloat16)
               for n in (3 * d, d))
    w = jnp.asarray(rng.randn(d, c["width"]) * 0.5, jnp.float32)
    rule = registry.get("causal_conv1d").lower

    def traced_now():
        def conv(v, w):
            return rule(None, {"X": [v], "Filter": [w]}, {})["Out"][0]

        def mixer(bcu, w):
            b, gate, u = jnp.split(bcu, 3, axis=-1)
            return gate * conv(b * u, w)

        def both(f):
            def run(x, w, dy):
                y, vjp = jax.vjp(f, x, w)
                return (y,) + vjp(dy)
            return run
        return conv, mixer, both

    def float32_mixer(bcu, w):
        b, gate, u = jnp.split(bcu, 3, axis=-1)
        return gate * plain.causal_conv(b * u, w)

    array_ms = 1e3 * t * d * 2 / 819e9     # one [T, D] bf16 array at 819 GB/s
    saved = os.environ.get("PADDLE_TPU_PALLAS")
    try:
        with jax.default_device(smoke.device):
            y, vjp = jax.vjp(float32_mixer, bcu.astype(jnp.float32), w)
            want = (y,) + vjp(dy.astype(jnp.float32))
            for path, flag in (("kernel", "conv"), ("xla", "0")):
                os.environ["PADDLE_TPU_PALLAS"] = flag
                conv, mixer, both = traced_now()
                run = jax.jit(both(mixer))
                errs = _normalized_errors(("y", "dbcu", "dw"),
                                          run(bcu, w, dy), want)
                if max(errs.values()) > 3e-2:
                    raise AssertionError(
                        "the gated convolution (%s path) is off its float32 "
                        "recomputation: %r" % (path, errs))
                v = bcu[..., :d]
                smoke.say(
                    "short_conv mixer without its matmuls, %s path, [1, %d, "
                    "3 x %d] bf16, %d taps: forward %.3f ms (least %.3f: 4 "
                    "arrays), forward + backward %.3f ms (least %.3f: 11 "
                    "arrays); the convolution's rule alone forward %.3f "
                    "ms, forward + backward %.3f ms; off float32 by %s"
                    % (path, t, d, c["width"],
                       _in_flight_ms(jax.jit(mixer), (bcu, w)), 4 * array_ms,
                       _in_flight_ms(run, (bcu, w, dy)), 11 * array_ms,
                       _in_flight_ms(jax.jit(conv), (v, w)),
                       _in_flight_ms(jax.jit(both(conv)), (v, w, dy)),
                       ", ".join("%s %.2e" % kv for kv in errs.items())))
    finally:
        if saved is None:
            os.environ.pop("PADDLE_TPU_PALLAS", None)
        else:
            os.environ["PADDLE_TPU_PALLAS"] = saved

    # the expert layer: 8 of 32 held, the router sigmoid with a bias
    x = jnp.asarray(rng.randn(t, d), jnp.bfloat16)
    router = jnp.asarray(rng.randn(d, e) * 0.02, jnp.float32)
    bias = jnp.asarray(rng.randn(e) * 0.05, jnp.float32)
    wg, wu = (jnp.asarray(rng.randn(held, d, c["f"]) * 0.02, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.randn(held, c["f"], d) * 0.02, jnp.float32)
    cfg = dict(num_experts=e, num_experts_per_tok=c["top_k"],
               norm_topk_prob=True, router_scoring="sigmoid", first_expert=0)

    def layer(x, router, wg, wu, wd):
        out, _, _, load = moe.routed_ffn(
            x, router, wg, wu, wd, c["top_k"], True,
            expert_dtype=jnp.bfloat16, scoring="sigmoid", expert_bias=bias)
        return out, load

    def trained(x, router, wg, wu, wd):
        def loss(*a):
            return layer(*a)[0].astype(jnp.float32).sum()
        return jax.grad(loss, argnums=(0, 1, 2, 3, 4))(x, router, wg, wu, wd)

    def routing(x, router):
        logits = jnp.dot(x.astype(jnp.float32), router,
                         precision=jax.lax.Precision.HIGHEST)
        _, _, gate, expert = moe._route(logits, c["top_k"], True, "sigmoid",
                                        bias, 1.0)
        return gate, jnp.argsort(expert.T.reshape(-1), stable=True)

    with jax.default_device(smoke.device):
        out, load = jax.jit(layer)(x, router, wg, wu, wd)
        with jax.default_matmul_precision("highest"):
            want, _, _, want_load = jax.jit(
                lambda *a: plain.routed_experts(*a, cfg, expert_bias=bias))(
                    x.astype(jnp.float32), router, wg, wu, wd)
        # a token whose choice moved under bf16 is off by a whole expert:
        # the error is the decided tokens', and the moved ones are counted
        off = jnp.abs(out.astype(jnp.float32) - want).max(-1) \
            / jnp.abs(want).max()
        moved = int(jnp.abs(load - want_load).sum()) // 2
        err = float(jnp.sort(off)[-1 - 4 * max(moved, t // 1000)])
        if int(load.sum()) != c["top_k"] * t or err > 3e-2 \
                or moved > t // 100:
            raise AssertionError(
                "the sigmoid-routed layer: %d assignments counted of %d, %d "
                "moved, output off by %.2e" % (load.sum(), c["top_k"] * t,
                                               moved, err))
        args = (x, router, wg, wu, wd)
        smoke.say(
            "expert layer alone, %d tokens of %d, top-%d of %d sigmoid "
            "scores + bias, %d held of width %d (%d rows): forward %.3f ms, "
            "forward + backward %.3f ms; the router's part (float32 matmul, "
            "sigmoid, top-k of s + b, gather from s, the sort) %.3f ms; off "
            "float32 by %.2e outside the tokens a moved assignment can have "
            "touched, %d assignments moved"
            % (t, d, c["top_k"], e, held, c["f"], load[:held].sum(),
               _in_flight_ms(jax.jit(layer), args),
               _in_flight_ms(jax.jit(trained), args),
               _in_flight_ms(jax.jit(routing), (x, router)), err, moved))
    _held_layer_times(
        smoke, dict(name="LFM2", n=t, d=d, e=e, held=held, f=c["f"],
                    top_k=c["top_k"], activation="silu", scoring="sigmoid"),
        expert_bias=bias)


def _token_ids(kind, rows, vocab, seed):
    """`rows` ids under one of the three traffics phase I reads: uniform
    over the vocabulary (the benchmark's), Zipf with exponent 1 over a
    shuffled vocabulary (packed text: the commonest word about a tenth of
    the rows), one id throughout (the longest run there is)."""
    rng = np.random.RandomState(seed)
    if kind == "uniform":
        return rng.randint(0, vocab, rows)
    if kind == "zipf":
        p = 1.0 / np.arange(1, vocab + 1)
        return rng.permutation(vocab)[rng.choice(vocab, rows, p=p / p.sum())]
    return np.full(rows, vocab // 3)


def phase_i(smoke):
    """lookup_table's rule alone: the gather and its backward (the dense
    float32 [V, D] gradient of the table) at each token cell's (rows,
    vocabulary, width), in flight, for uniform, Zipf and one-id traffic:
    as the rule runs it (XLA's scatter, or `ptpu_embedding_grad` where the
    rule hands it the table), with jnp.take's own transpose and with the
    kernel whatever the rule says, beside the time the bytes take at the
    chip's HBM rate (the gradient written once, the rows read and written
    forward and read backward). The gradient of sixteen vocabulary rows
    (the commonest ids and ids that do not occur) is held to float64 sums
    on the host, and the whole of it to jnp.take's own. The times are
    printed for the next reader (no metric, and no check: with one id
    throughout the kernel's one busy block adds every row while nothing is
    written, 0.1-0.25 ms that uniform ids hide under the writes): PERF.md
    sections 5 and 6 quote them."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core import registry
    from paddle_tpu.ops.embedding_grad import dense_grad, grad_form

    rule = registry.get("lookup_table").lower

    def with_rule(w, ids, g):
        y, vjp = jax.vjp(lambda w: rule(
            registry.AbstractCtx(), {"W": [w], "Ids": [ids]},
            {"padding_idx": -1})["Out"][0], w)
        return y, vjp(g)[0]

    def with_take(w, ids, g):
        y, vjp = jax.vjp(lambda w: jnp.take(w, ids[:, 0], axis=0), w)
        return y, vjp(g)[0]

    def forward(w, ids, g):
        return jnp.take(w, ids[:, 0], axis=0)

    def with_kernel(w, ids, g):
        return (jnp.take(w, ids[:, 0], axis=0),
                dense_grad(ids[:, 0], g, w.shape[0]))

    runs = {"forward": jax.jit(forward), "rule": jax.jit(with_rule),
            "take": jax.jit(with_take), "kernel": jax.jit(with_kernel)}
    for name, rows, vocab, width in smoke.cfg["embedding"]:
        rng = np.random.RandomState(41)
        w = jnp.asarray(rng.randn(vocab, width), jnp.float32)
        g_host = rng.randn(rows, width).astype(np.float32)
        g = jnp.asarray(g_host)
        floor = 1e3 * 4.0 * (vocab + 3 * rows) * width / 819e9
        times = {}
        for kind in ("uniform", "zipf", "one"):
            ids_host = _token_ids(kind, rows, vocab, seed=43)
            ids = jnp.asarray(ids_host[:, None], jnp.int32)
            times[kind] = {k: _in_flight_ms(f, (w, ids, g))
                           for k, f in runs.items()}
            counts = np.bincount(ids_host, minlength=vocab)
            probe = np.concatenate([np.argsort(-counts)[:8],
                                    np.flatnonzero(counts == 0)[:8]])
            want = np.stack([g_host[ids_host == v].sum(0, dtype=np.float64)
                             for v in probe])
            own = runs["take"](w, ids, g)[1]
            for k in ("rule", "kernel"):
                dw = runs[k](w, ids, g)[1]
                got = np.asarray(dw[jnp.asarray(probe)], np.float64)
                err = float(np.abs(got - want).max() / np.abs(want).max())
                apart = float(jnp.max(jnp.abs(dw - own))
                              / jnp.max(jnp.abs(own)))
                del dw
                if err > 1e-5 or apart > 1e-5:
                    raise AssertionError(
                        "%s, %s ids, %s: the gradient is %.2e off float64 "
                        "sums and %.2e off jnp.take's own"
                        % (name, kind, k, err, apart))
            del own
        smoke.say("I lookup_table %s: %d rows of [%d, %d], gradient by %s; "
                  "forward + backward ms as the rule runs it (jnp.take's "
                  "own, the kernel; of which the forward; the bytes' floor "
                  "%.2f): %s"
                  % (name, rows, vocab, width, grad_form(rows, width), floor,
                     ", ".join("%s %.3f (%.3f, %.3f; %.3f)"
                               % (k, t["rule"], t["take"], t["kernel"],
                                  t["forward"])
                               for k, t in times.items())))


def phase_j(smoke):
    """The latent form of the flash kernels and the hyper-connections'
    kernels, each alone at the Xing4.0 cell's shapes, against float32
    references on the same (bf16-rounded) inputs; times in flight, printed
    for the next reader."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import mhc_kernels, pallas_kernels
    from paddle_tpu.parallel.ring_attention import attention_reference

    c = smoke.cfg["latent"]
    t, h, d, dr, tol = c["t"], c["h"], c["d"], c["dr"], c["tol"]
    keys = jax.random.split(jax.random.key(43), 12)
    bf = jnp.bfloat16

    def draw(key, shape, scale=1.0):
        return (scale * jax.random.normal(key, shape, jnp.float32)).astype(bf)

    q, k, v, g = (draw(keys[i], (1, t, h, d)) for i in range(4))
    qr, kr = draw(keys[4], (1, t, h, dr)), draw(keys[5], (1, t, 1, dr))
    scale = 0.14468

    def flash(q, k, v, qr, kr):
        return pallas_kernels.flash_attention(
            q, k, v, causal=True, scale=scale, q_rope=qr, k_rope=kr)

    def dense(q, k, v, qr, kr):     # float32, the naive way, a head at once
        qq = jnp.concatenate([q, qr], -1).astype(jnp.float32)
        kk = jnp.concatenate([k, jnp.broadcast_to(kr, qr.shape)],
                             -1).astype(jnp.float32)
        with jax.default_matmul_precision("highest"):
            return jax.lax.map(
                lambda xs: attention_reference(
                    xs[0][None, :, None], xs[1][None, :, None],
                    xs[2][None, :, None], causal=True, scale=scale)[0, :, 0],
                (qq[0].transpose(1, 0, 2), kk[0].transpose(1, 0, 2),
                 v[0].astype(jnp.float32).transpose(1, 0, 2))
            ).transpose(1, 0, 2)[None]

    def both(fn):
        def run(*args):
            out, vjp = jax.vjp(fn, *args)
            return (out,) + vjp(g.astype(out.dtype))
        return jax.jit(run)

    names = ("out", "dq", "dk", "dv", "dq_rope", "dk_rope")
    got = both(flash)(q, k, v, qr, kr)
    want = both(dense)(q, k, v, qr, kr)
    errors = _normalized_errors(names, got, want)
    worst = max(errors.values())
    ms = {"forward": _in_flight_ms(jax.jit(flash), (q, k, v, qr, kr)),
          "forward + backward": _in_flight_ms(both(flash),
                                              (q, k, v, qr, kr))}
    plain = jax.jit(lambda q, k, v: pallas_kernels.flash_attention(
        q, k, v, causal=True))
    ms["forward at 128 | 128, no rotary part"] = _in_flight_ms(plain,
                                                                (q, k, v))
    smoke.say("J flash at %d | %d with one rotary key, [1, %d, %d]: off the "
              "float32 dense attention by %s (tolerance %g); ms %s"
              % (d + dr, d, t, h, ", ".join(
                  "%s %.2e" % kv for kv in errors.items()), tol,
                 ", ".join("%s %.3f" % kv for kv in ms.items())))
    if not worst <= tol:
        raise AssertionError("the latent flash kernels are %.2e off" % worst)

    n, width, iters = c["streams"], c["c"], c["iters"]
    kk = mhc_kernels.columns(n)
    x = draw(keys[6], (t, n * width))
    phi = 0.02 * jax.random.normal(keys[7], (n * width, kk), jnp.float32)
    alpha = jnp.full((3,), 0.5, jnp.float32)
    bias = 0.5 * jax.random.normal(keys[8], (kk,), jnp.float32)
    y, gh = draw(keys[9], (t, width)), draw(keys[10], (t, width))
    gx = draw(keys[11], (t, n * width))
    args = (n, iters, 1e-6, (-30.0, 30.0))

    def layer(kernels, dtype):
        def run(x, phi, alpha, bias, y):
            x, y = x.astype(dtype), y.astype(dtype)
            read, coef, stream = mhc_kernels.pre(x, phi, alpha, bias, *args,
                                                 kernels)
            out = mhc_kernels.post(stream, y, coef, n, kernels)
            return read, out, coef

        def with_grads(x, phi, alpha, bias, y):
            (read, out, coef), vjp = jax.vjp(run, x, phi, alpha, bias, y)
            return (read, out, coef) + vjp((
                gh.astype(dtype), gx.astype(dtype), jnp.zeros_like(coef)))
        return jax.jit(run), jax.jit(with_grads)

    fwd, full = layer(True, bf)
    _, reference = layer(False, jnp.float32)
    names = ("h", "x_out", "coef", "dx", "dphi", "dalpha", "dbias", "dy")
    errors = _normalized_errors(names, full(x, phi, alpha, bias, y),
                                reference(x, phi, alpha, bias, y))
    worst = max(errors.values())
    parts = {
        "pre": jax.jit(lambda x: mhc_kernels.pre(x, phi, alpha, bias, *args,
                                                 True)[:2]),
        "expand": jax.jit(lambda y: mhc_kernels.expand(y, n, True)),
        "reduce": jax.jit(lambda x: mhc_kernels.reduce(x, n, True))}
    ms = {"forward": _in_flight_ms(fwd, (x, phi, alpha, bias, y)),
          "forward + backward": _in_flight_ms(full, (x, phi, alpha, bias, y)),
          "pre alone": _in_flight_ms(parts["pre"], (x,)),
          "expand": _in_flight_ms(parts["expand"], (y,)),
          "reduce": _in_flight_ms(parts["reduce"], (x,))}
    # the kernels' one tile, rows a block: forward + backward at each
    from paddle_tpu.ops import kernel_config
    tile = kernel_config.DEFAULT_TILES["mhc"]
    chosen, by_rows = tile["block_rows"], {}
    try:
        for rows in (64, 128, 256):
            if t % rows:
                continue
            tile["block_rows"] = rows
            try:
                by_rows[rows] = "%.3f" % _in_flight_ms(
                    layer(True, bf)[1], (x, phi, alpha, bias, y))
            except Exception as e:  # noqa: BLE001 — Mosaic refused the tile
                by_rows[rows] = "refused (%s)" % type(e).__name__
    finally:
        tile["block_rows"] = chosen
    ms["forward + backward by rows a block"] = json.dumps(by_rows)
    stream_ms = 1e3 * x.size * 2 / 819e9
    smoke.say("J hyper-connection of %d streams, [%d, %d] bf16 (a stream "
              "array's bytes take %.3f ms): off the float32 jax.numpy passes "
              "by %s (tolerance %g); ms %s"
              % (n, t, n * width, stream_ms, ", ".join(
                  "%s %.2e" % kv for kv in errors.items()), tol,
                 ", ".join("%s %s" % (k, v if isinstance(v, str)
                                      else "%.3f" % v)
                           for k, v in ms.items())))
    if not worst <= tol:
        raise AssertionError("the mhc kernels are %.2e off" % worst)


def phase_l(smoke):
    """The latent core where the part without position (192) is not the
    value's width (256), at the GLM-4.7-Flash cell's shape, in the form
    pallas_kernels.flash_attention runs it in (whole heads) and in the form
    that lost to it (two_part: padded here to the value's width for the
    two-part kernels): each against the float32 dense attention on the
    same rounded inputs, and timed."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels
    from paddle_tpu.parallel.ring_attention import attention_reference

    c = smoke.cfg["latent_unequal"]
    b, t, h, d, dr, dv = (c[k] for k in ("b", "t", "h", "d", "dr", "dv"))
    keys = jax.random.split(jax.random.key(49), 6)
    bf = jnp.bfloat16
    q, k = (jax.random.normal(keys[i], (b, t, h, d), jnp.float32).astype(bf)
            for i in range(2))
    v, g = (jax.random.normal(keys[i], (b, t, h, dv), jnp.float32).astype(bf)
            for i in (2, 3))
    qr = jax.random.normal(keys[4], (b, t, h, dr), jnp.float32).astype(bf)
    kr = jax.random.normal(keys[5], (b, t, 1, dr), jnp.float32).astype(bf)
    scale = (d + dr) ** -0.5

    def flash(form):
        bq, bk = c["blocks"][form]
        lanes = [(0, 0)] * 3 + [(0, dv - d if form == "two_part" else 0)]
        return lambda q, k, v, qr, kr: pallas_kernels.flash_attention(
            jnp.pad(q, lanes), jnp.pad(k, lanes), v, causal=True,
            scale=scale, q_rope=qr, k_rope=kr, block_q=bq, block_k=bk)

    def dense(q, k, v, qr, kr):     # float32, a (sequence, head) at once
        qq = jnp.concatenate([q, qr], -1).astype(jnp.float32)
        kk = jnp.concatenate([k, jnp.broadcast_to(kr, qr.shape)],
                             -1).astype(jnp.float32)

        def rows(x):                # [B, T, H, D] -> [B * H, T, D]
            return x.transpose(0, 2, 1, 3).reshape(b * h, t, -1)
        with jax.default_matmul_precision("highest"):
            out = jax.lax.map(
                lambda xs: attention_reference(
                    xs[0][None, :, None], xs[1][None, :, None],
                    xs[2][None, :, None], causal=True, scale=scale)[0, :, 0],
                (rows(qq), rows(kk), rows(v.astype(jnp.float32))))
        return out.reshape(b, h, t, dv).transpose(0, 2, 1, 3)

    def both(fn):
        def run(*args):
            out, vjp = jax.vjp(fn, *args)
            return (out,) + vjp(g.astype(out.dtype))
        return jax.jit(run)

    names = ("out", "dq", "dk", "dv", "dq_rope", "dk_rope")
    args = (q, k, v, qr, kr)
    want = both(dense)(*args)
    worst, found = 0.0, []
    for form in ("whole", "two_part"):
        full = both(flash(form))
        errors = _normalized_errors(names, full(*args), want)
        worst = max(worst, *errors.values())
        found.append("%s at blocks %s off by %s; ms forward %.3f, forward + "
                     "backward %.3f" % (
                         form, "%d x %d" % c["blocks"][form], ", ".join(
                             "%s %.2e" % kv for kv in errors.items()),
                         _in_flight_ms(jax.jit(flash(form)), args),
                         _in_flight_ms(full, args)))
    # heads that arrive joined: the plain kernels alone
    joined = (jnp.concatenate([q, qr], -1),
              jnp.concatenate([k, jnp.broadcast_to(kr, qr.shape)], -1), v)

    def plain(q, k, v):
        return pallas_kernels.flash_attention(q, k, v, causal=True,
                                              scale=scale)
    found.append("heads of %d arriving joined: ms forward %.3f, forward + "
                 "backward %.3f" % (dv, _in_flight_ms(jax.jit(plain), joined),
                                    _in_flight_ms(both(plain), joined)))
    smoke.say("L latent core at %d + %d on %d, [%d, %d, %d] (tolerance %g, "
              "against the float32 dense attention): %s"
              % (d, dr, dv, b, t, h, c["tol"], "; ".join(found)))
    if not worst <= c["tol"]:
        raise AssertionError("the latent core at unequal widths is %.2e off"
                             % worst)


def phase_k(smoke):
    """One output head with its loss, forward + backward (the matmul, the
    loss, dX and dW), bf16 operands as under AMP, in two forms of
    `softmax_with_cross_entropy`: `parent`, PR 43's (the logits upcast to
    float32 in HBM, the kernel, a dense Softmax beside it that gets a
    cotangent of zeros) and `now`, PR 44's (bf16 logits into the kernel, no
    Softmax); dlogits is jax.numpy in both, fused by XLA into the two
    gradient matmuls. Then the kernel alone, rows a grid step swept, beside
    the time its bytes take at 819 GB/s. Loss and dlogits are held to the
    float32 formula on the same bf16 logits, at a ragged N too. Times are
    printed for the next reader (PERF.md section 6, PR 44, which also has
    the third form's: a second kernel that wrote dlogits once)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels as pk

    bf16, f32 = jnp.bfloat16, jnp.float32
    c = smoke.cfg["head"]

    def formula(x, lab, g):
        x = x.astype(f32)
        lse = jax.nn.logsumexp(x, axis=-1, keepdims=True)
        loss = lse - jnp.take_along_axis(x, lab[:, None], axis=1)
        d = (jnp.exp(x - lse) - jax.nn.one_hot(lab, x.shape[1])) * g
        return loss, d

    def both_ways(x, lab, g):
        loss, vjp = jax.vjp(lambda x: pk.softmax_xent(x, lab), x)
        return loss, vjp(g)[0]

    def head(form):
        def f(h, w, lab):
            x = jnp.dot(h, w.astype(bf16),
                        preferred_element_type=f32).astype(bf16)
            if form == "now":
                return jnp.mean(pk.softmax_xent(x, lab)), None
            x = x.astype(f32)
            return (jnp.mean(pk.softmax_xent(x, lab)),
                    jnp.exp(jax.nn.log_softmax(x, axis=-1)))

        def run(h, w, lab):
            (loss, dense), vjp = jax.vjp(lambda h, w: f(h, w, lab), h, w)
            return (loss,) + vjp((jnp.ones((), f32),
                                  None if dense is None
                                  else jnp.zeros_like(dense)))
        return jax.jit(run)

    for n, v in [s[::2] for s in c["shapes"]] + [c["ragged"]]:
        rng = np.random.RandomState(47)
        x = jnp.asarray(rng.randn(n, v) * 3.0, bf16)
        lab = jnp.asarray(rng.randint(0, v, n), jnp.int32)
        g = jnp.asarray(rng.rand(n, 1) / n, f32)
        want_loss, want_d = jax.jit(formula)(x, lab, g)
        loss, d = jax.jit(both_ways)(x, lab, g)
        err_l = float(jnp.max(jnp.abs(loss - want_loss))
                      / jnp.max(jnp.abs(want_loss)))
        err_d = float(jnp.max(jnp.abs(d.astype(f32) - want_d))
                      / jnp.max(jnp.abs(want_d)))
        if d.dtype != bf16 or err_l > 1e-5 or err_d > 1e-2:
            raise AssertionError(
                "softmax_xent [%d, %d]: loss %.2e and dlogits (%s) %.2e off "
                "the float32 formula" % (n, v, err_l, d.dtype, err_d))
        del want_d, d
        times = []
        for rows in (None,) + tuple(c["rows"]):
            run = jax.jit(lambda x, lab, rows=rows: pk.softmax_xent(
                x, lab, block_n=rows))
            try:
                off = float(jnp.max(jnp.abs(run(x, lab) - want_loss)))
            except Exception as e:  # noqa: BLE001 — a tile Mosaic refuses
                times.append("%s rows: refused (%s)"
                             % (rows, str(e).splitlines()[0][:80]))
                continue
            if off > 1e-4:
                raise AssertionError("softmax_xent [%d, %d] at %s rows: "
                                     "loss %.2e off" % (n, v, rows, off))
            times.append("%s rows %.3f" % (rows or "the table's",
                                           _in_flight_ms(run, (x, lab))))
        smoke.say("K softmax_xent kernel [%d, %d] bf16, loss %.1e and "
                  "dlogits %.1e off the formula; forward ms (its bytes take "
                  "%.3f): %s" % (n, v, err_l, err_d,
                                 1e3 * n * v * 2 / 819e9, "; ".join(times)))
        del x, want_loss
    for n, width, v in c["shapes"]:
        rng = np.random.RandomState(53)
        h = jnp.asarray(rng.randn(n, width), bf16)
        w = jnp.asarray(rng.randn(width, v) * 0.02, f32)
        lab = jnp.asarray(rng.randint(0, v, n), jnp.int32)
        peak = 1e3 * 3 * 2.0 * n * width * v / 197e12
        out = {}
        for form in ("now", "parent"):
            run = head(form)
            out[form] = jax.block_until_ready(run(h, w, lab))
            out[form] = (out[form], _in_flight_ms(run, (h, w, lab), calls=2))
        for a, b, what in zip(out["parent"][0], out["now"][0],
                              ("loss", "dX", "dW")):
            err = float(jnp.max(jnp.abs(a.astype(f32) - b.astype(f32)))
                        / jnp.max(jnp.abs(b.astype(f32))))
            if err > 2e-2:
                raise AssertionError(
                    "head [%d, %d] x [%d, %d]: the parent's %s is %.2e off"
                    % (n, width, width, v, what, err))
        smoke.say("K head [%d, %d] x [%d, %d], forward + backward ms (three "
                  "matmuls at the peak %.2f): %s"
                  % (n, width, width, v, peak,
                     ", ".join("%s %.3f" % (k, t[1])
                               for k, t in out.items())))
        del out


def phase_m(smoke):
    """A layer's nine expert matmuls alone, route against route."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import expert_gmm

    c = smoke.cfg["expert_gmm"]
    bf = jnp.bfloat16
    for name, n, d, e, held, f, top_k in c["shapes"]:
        keys = jax.random.split(jax.random.key(50), 8)
        # a router at initialisation with a lean of its own an expert
        logits = jax.random.normal(keys[0], (n, e)) \
            + 0.5 * jax.random.normal(keys[1], (e,))
        chosen = jax.lax.top_k(logits, top_k)[1].reshape(-1)
        sizes = jnp.sum(chosen[:, None] == jnp.arange(held), axis=0,
                        dtype=jnp.int32)
        total, rows = int(sizes.sum()), top_k * n
        x, dy = (jax.random.normal(k, (rows, d), jnp.float32).astype(bf)
                 for k in keys[2:4])
        w_in = (jax.random.normal(keys[4], (held, d, f)) * d ** -0.5) \
            .astype(bf)
        w_out = (jax.random.normal(keys[5], (held, f, d)) * f ** -0.5) \
            .astype(bf)

        def ragged(lhs, rhs):
            return jax.lax.ragged_dot(lhs, rhs, sizes,
                                      preferred_element_type=lhs.dtype)

        def ragged_t(lhs, rhs, dout):
            return jax.vjp(ragged, lhs, rhs)[1](dout)

        def passes(fwd, d_rows, d_weights):
            """The three passes over (gate, up, down), each one program."""
            def forward(x, h, w_in, w_out):
                return fwd(x, w_in), fwd(x, w_in), fwd(h, w_out)

            def rows_t(dh, dy, w_in, w_out):
                return (d_rows(dh, w_in), d_rows(dh, w_in),
                        d_rows(dy, w_out))

            def weights_t(x, h, dh, dy):
                return (d_weights(x, dh), d_weights(x, dh),
                        d_weights(h, dy))

            return jax.jit(forward), jax.jit(rows_t), jax.jit(weights_t)

        routes = {"ragged_dot": passes(
            ragged, lambda dout, rhs: ragged_t(jnp.zeros(
                (rows, rhs.shape[1]), bf), rhs, dout)[0],
            lambda lhs, dout: ragged_t(lhs, jnp.zeros(
                (held, lhs.shape[1], dout.shape[1]), bf), dout)[1])}
        for block_m in c["block_m"]:
            plan = expert_gmm.plan(sizes, rows, block_m)
            routes["expert_gmm at %d rows" % block_m] = passes(
                lambda lhs, rhs, p=plan: expert_gmm.gmm(lhs, rhs, p),
                lambda dout, rhs, p=plan: expert_gmm.gmm_drows(dout, rhs, p),
                lambda lhs, dout, p=plan: expert_gmm.gmm_dweights(
                    lhs, dout, p))
        h, dh = (jax.random.normal(k, (rows, f), jnp.float32).astype(bf)
                 for k in keys[6:8])
        args = ((x, h, w_in, w_out), (dh, dy, w_in, w_out), (x, h, dh, dy))
        # as many calls in flight as leave the device room for their results
        calls = max(2, min(10, (4 << 30) // (3 * rows * max(d, f) * 2)))
        ops = 3 * 2 * total * d * f
        want = None
        smoke.say("expert matmuls, %s: %d of %d rows in %d groups of %d to "
                  "%d, [%d x %d]" % (name, total, rows, held,
                                     int(sizes.min()), int(sizes.max()),
                                     d, f))
        for route, runs in routes.items():
            got = [run(*a) for run, a in zip(runs, args)]
            # what lies past the groups belongs to no one
            got = [[o[:total] if o.shape[0] == rows else o for o in outs]
                   for outs in got]
            ms = [_in_flight_ms(run, a, calls=calls)
                  for run, a in zip(runs, args)]
            if want is None:
                want, errs = got, []
            else:
                errs = [float(jnp.max(jnp.abs(
                    a.astype(jnp.float32) - b.astype(jnp.float32)))
                    / (jnp.max(jnp.abs(b.astype(jnp.float32))) + 1e-6))
                    for outs, refs in zip(got, want)
                    for a, b in zip(outs, refs)]
            smoke.say("  %-26s forward %.3f ms %.1f TFLOP/s, d rows %.3f ms "
                      "%.1f, d weights %.3f ms %.1f, the nine %.3f ms %.1f%s"
                      % ((route,) + tuple(itertools.chain.from_iterable(
                          (t, done / t / 1e9) for t, done in zip(
                              ms + [sum(ms)], [ops] * 3 + [3 * ops])))
                         + ("; largest difference %.2e" % max(errs)
                            if errs else "",)))
            if errs and not max(errs) <= c["tol"]:
                raise AssertionError(
                    "%s disagrees with ragged_dot at %s: %r (tol %g)"
                    % (route, name, errs, c["tol"]))
            del got


# --------------------------------------------------------------- phase N --
def phase_n(smoke):
    """The selective scan's kernels against lax.scan over tokens, and one
    differential attention core in the builder's form against the float32
    dense two-map form, at the Phi-4-mini-flash cell's shapes; each
    timed."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels
    from paddle_tpu.ops import selective_scan_kernels as scan
    from paddle_tpu.ops.kernel_config import DEFAULT_TILES

    c = smoke.cfg["selective_scan"]
    b, t, ch, n = (c[k] for k in ("b", "t", "c", "n"))
    keys = jax.random.split(jax.random.key(54), 7)
    with jax.default_device(smoke.device):
        x = jax.random.normal(keys[0], (b, t, ch))
        delta = jax.nn.softplus(jax.random.normal(keys[1], (b, t, ch)) - 4.0)
        a = -jnp.broadcast_to(jnp.arange(1.0, n + 1), (ch, n))
        bm, cm = (jax.random.normal(k, (b, t, n)) for k in keys[2:4])
        d = 1.0 + 0.1 * jax.random.normal(keys[4], (ch,))
        dy = jax.random.normal(keys[5], (b, t, ch))
        args = (x, delta, a, bm, cm, d)

        def both(path):
            def run(*args):
                y, vjp = jax.vjp(lambda *v: scan.selective_scan(
                    *v, path=path), *args)
                return (y,) + vjp(dy)
            return jax.jit(run)

        names = ("y", "dx", "ddelta", "da", "db", "dc", "dd")
        errors = _normalized_errors(names, both("kernel")(*args),
                                    both("xla")(*args))
        forward = jax.jit(lambda *v: scan.selective_scan(*v, path="kernel"))
        smoke.say("N selective scan kernels, x %s, %d states, chunks of %d "
                  "(tolerance %g, against lax.scan over tokens on this "
                  "device): off by %s; ms forward %.3f, forward + backward "
                  "%.3f" % (
                      [b, t, ch], n, DEFAULT_TILES["scan"]["chunk"], c["tol"],
                      ", ".join(
                          "%s %.2e" % kv for kv in errors.items()),
                      _in_flight_ms(forward, args),
                      _in_flight_ms(both("kernel"), args)))
        if not max(errors.values()) <= c["tol"]:
            raise AssertionError("the selective scan's kernels are %.2e off "
                                 "lax.scan" % max(errors.values()))

        c = smoke.cfg["differential"]
        b, t, pairs, kvp, hd = (c[k] for k in ("b", "t", "pairs", "kv_pairs",
                                               "hd"))
        group, bf = pairs // kvp, jnp.bfloat16
        q = jax.random.normal(keys[0], (b, t, kvp, group, 2, hd)).astype(bf)
        k = jax.random.normal(keys[1], (b, t, kvp, 2, hd)).astype(bf)
        v = jax.random.normal(keys[2], (b, t, kvp, 2 * hd)).astype(bf)
        g = jax.random.normal(keys[3], (b, t, kvp, 2, group, 2 * hd))
        lam = 0.3

        def built(window):
            """models/causal_lm.py differential_attention's core."""
            def core(q, k, v):
                pad = [(0, 0)] * 3 + [(0, hd)]
                qq = jnp.pad(q.transpose(0, 1, 2, 4, 3, 5).reshape(
                    b, t, 2 * pairs, hd), pad)
                kk = jnp.pad(k.reshape(b, t, 2 * kvp, hd), pad)
                vv = jnp.broadcast_to(v[:, :, :, None], (b, t, kvp, 2, 2 * hd)
                                      ).reshape(b, t, 2 * kvp, 2 * hd)
                ctx = pallas_kernels.flash_attention(
                    qq, kk, vv, causal=True, window=window, scale=hd ** -0.5)
                ctx = ctx.reshape(b, t, kvp, 2, group, 2 * hd)
                return ctx[:, :, :, 0] - lam * ctx[:, :, :, 1]
            return core

        def dense(window):
            def core(q, k, v):          # float32, a query pair at a time
                age = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
                visible = (age >= 0) if window is None \
                    else (age >= 0) & (age < window)

                @jax.checkpoint         # or a pair's two maps are kept
                def one(xs):            # q, k [T, 2, hd], v [T, 2 hd]
                    q, k, v = (x.astype(jnp.float32) for x in xs)
                    s = jnp.einsum("qjd,kjd->jqk", q, k) * hd ** -0.5
                    p = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), -1)
                    ctx = jnp.einsum("jqk,kd->jqd", p, v)
                    return ctx[0] - lam * ctx[1]
                with jax.default_matmul_precision("highest"):
                    out = jax.lax.map(one, (
                        q[0].reshape(t, pairs, 2, hd).transpose(1, 0, 2, 3),
                        jnp.repeat(k[0], group, axis=1).transpose(1, 0, 2, 3),
                        jnp.repeat(v[0], group, axis=1).transpose(1, 0, 2)))
                return out.transpose(1, 0, 2).reshape(
                    1, t, kvp, group, 2 * hd)
            return core

        def with_grads(fn):
            def run(*args):
                out, vjp = jax.vjp(fn, *args)
                return (out,) + vjp(g[:, :, :, 0].astype(out.dtype))
            return jax.jit(run)

        worst, found = 0.0, []
        for window in (c["window"], None):
            errors = _normalized_errors(
                ("out", "dq", "dk", "dv"),
                with_grads(built(window))(q, k, v),
                with_grads(dense(window))(q, k, v))
            worst = max(worst, *errors.values())
            found.append("window %s off by %s; ms forward %.3f, forward + "
                         "backward %.3f" % (
                             window, ", ".join("%s %.2e" % kv
                                               for kv in errors.items()),
                             _in_flight_ms(jax.jit(built(window)), (q, k, v)),
                             _in_flight_ms(with_grads(built(window)),
                                           (q, k, v))))
        smoke.say("N differential core, %d pairs on %d of %d, T=%d, as %d "
                  "heads of %d (tolerance %g, against the float32 dense two "
                  "maps): %s" % (pairs, kvp, hd, t, 2 * pairs, 2 * hd,
                                 c["tol"], "; ".join(found)))
        if not worst <= c["tol"]:
            raise AssertionError("the differential core is %.2e off" % worst)


def phase_o(smoke):
    """The state-space-dual scan's two kernels (bf16 operands, as a cell
    runs them) against the recurrence token by token in float32 on this
    device, forward and the six gradients, under the decay a layer starts
    with and under Delta A = -6 a token; then timed, forward and forward +
    backward, at the table's tile and at the tiles of the sweep."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.causal_lm_reference import ssd_scan as plain
    from paddle_tpu.ops import ssd_kernels as ssd
    from paddle_tpu.ops.kernel_config import DEFAULT_TILES

    c = smoke.cfg["ssd"]
    b, t, h, p, n = (c[k] for k in ("b", "t", "h", "p", "n"))
    keys = jax.random.split(jax.random.key(57), 6)
    bf = jnp.bfloat16
    with jax.default_device(smoke.device):
        x = jax.random.normal(keys[0], (b, t, h, p)).astype(bf)
        bm, cm = (jax.random.normal(k, (b, t, n)).astype(bf)
                  for k in keys[1:3])
        d = 1.0 + 0.1 * jax.random.normal(keys[3], (h,))
        dy = jax.random.normal(keys[4], (b, t, h, p))
        starts = jnp.exp(jax.random.uniform(
            keys[5], (b, t, h), minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
        decays = {"a layer's start": (starts, -jnp.linspace(1.0, h, h)),
                  "Delta A = -6 a token": (jnp.ones((b, t, h)),
                                           jnp.full((h,), -6.0))}

        def both(fn):
            def run(*args):
                y, vjp = jax.vjp(fn, *args)
                return (y,) + vjp(dy)
            return jax.jit(run)

        def kernels(*v):
            return ssd.ssd_scan(*v, operand_dtype=bf, path="kernel")

        def tokens(*v):
            with jax.default_matmul_precision("highest"):
                return plain(*(u.astype(jnp.float32) for u in v),
                             segment=c["segment"])

        names = ("y", "dx", "ddelta", "da", "db", "dc", "dd")
        worst = 0.0
        for name, (delta, a) in decays.items():
            args = (x, delta, a, bm, cm, d)
            errors = _normalized_errors(names, both(kernels)(*args),
                                        both(tokens)(*args))
            worst = max(worst, *errors.values())
            smoke.say("O ssd kernels, x %s, %d states, %s (tolerance %g, "
                      "against the recurrence token by token in float32 on "
                      "this device): off by %s" % (
                          [b, t, h, p], n, name, c["tol"], ", ".join(
                              "%s %.2e" % kv for kv in errors.items())))
        args = (x,) + decays["a layer's start"] + (bm, cm, d)
        tiles = DEFAULT_TILES["ssd"]
        table = dict(tiles)
        try:
            for chunk, block_h in ((table["chunk"], table["block_h"]),) \
                    + tuple(c["sweep"]):
                tiles.update(chunk=chunk, block_h=block_h)
                smoke.say("O ssd kernels at chunks of %d, %d heads a grid "
                          "step: ms forward %.3f, forward + backward %.3f"
                          % (chunk, block_h,
                             _in_flight_ms(jax.jit(kernels), args),
                             _in_flight_ms(both(kernels), args)))
        finally:
            tiles.update(table)
        if not worst <= c["tol"]:
            raise AssertionError("the ssd kernels are %.2e off the "
                                 "recurrence" % worst)


def _bf16_ulps(a, b):
    """(elements that differ, the largest difference in units of the last
    place) of two bf16 arrays."""
    a, b = (np.asarray(v).view(np.int16).astype(np.int32) for v in (a, b))
    a, b = (np.where(v < 0, -32768 - v, v) for v in (a, b))
    off = np.abs(a - b)
    return int((off > 0).sum()), int(off.max())


def phase_p(smoke):
    """rotary_embedding's rule on its two paths, the Pallas pass (Mosaic on
    a TPU) and the jax.numpy lines (XLA), on the same bf16 x and dy: y and
    dx are held EQUAL, element for element, or off by one unit in the last
    place on at most 1 element in 10,000; then both are timed. x comes and
    goes as [B*T, H*D] rows, as the projection beside the op writes and
    reads it."""
    import types
    import jax
    import jax.numpy as jnp
    import paddle_tpu.ops  # noqa: F401 — registers the rule
    from paddle_tpu.core import registry
    from paddle_tpu.ops import kernel_config
    from paddle_tpu.ops.nn_ops import rotary_path

    c = smoke.cfg["rope"]
    rule = registry.get("rotary_embedding").lower
    ctx = types.SimpleNamespace(mesh=None, amp=False)
    was = os.environ.get("PADDLE_TPU_PALLAS")
    bf = jnp.bfloat16

    def on_path(path, shape, base):
        """(forward, forward + transpose) of the rule traced on `path`:
        rows [B*T, H*D] in, rows out."""
        def turn(x, pos):
            os.environ["PADDLE_TPU_PALLAS"] = "rope" if path == "kernel" \
                else "0"
            x4 = x.reshape(shape)
            assert rotary_path(ctx, x4, pos, {}) == path
            return rule(ctx, {"X": [x4], "Pos": [pos]},
                        {"base": base})["Out"][0].reshape(x.shape)

        def both(x, pos, dy):
            y, vjp = jax.vjp(lambda x: turn(x, pos), x)
            return y, vjp(dy)[0]
        return jax.jit(turn), jax.jit(both)

    failed = []
    try:
        with jax.default_device(smoke.device):
            for label, b, t, h, d, base in c["cases"]:
                keys = jax.random.split(jax.random.key(70 + h), 3)
                x, dy = (jax.random.normal(k, (b * t, h * d)).astype(bf)
                         for k in keys[:2])
                pos = jax.random.permutation(
                    keys[2], b * t).reshape(b, t).astype(jnp.int32)
                shape = (b, t, h, d)
                k_fwd, k_both = on_path("kernel", shape, base)
                x_fwd, x_both = on_path("xla", shape, base)
                got, want = k_both(x, pos, dy), x_both(x, pos, dy)
                counts = []
                for name, u, v in zip(("y", "dx"), got, want):
                    n, worst = _bf16_ulps(u, v)
                    counts.append("%s %d of %d differ (largest %d ulp)"
                                  % (name, n, u.size, worst))
                    if n and (worst > 1 or n * 10000 > u.size):
                        failed.append("%s %s" % (label, counts[-1]))
                moved = 2 * x.size * x.dtype.itemsize + 2 * b * t * d * 4
                smoke.say(
                    "P rotary %s %s bf16, base %g: %s; ms forward kernel "
                    "%.3f, xla %.3f; forward + transpose kernel %.3f, xla "
                    "%.3f; a pass's bytes at 819 GB/s %.3f"
                    % (label, list(shape), base, "; ".join(counts),
                       _in_flight_ms(k_fwd, (x, pos)),
                       _in_flight_ms(x_fwd, (x, pos)),
                       _in_flight_ms(k_both, (x, pos, dy)),
                       _in_flight_ms(x_both, (x, pos, dy)),
                       1e3 * moved / 819e9))
            label, b, t, h, d, base = c["cases"][0]
            tiles = kernel_config.DEFAULT_TILES["rope"]
            table = dict(tiles)
            try:
                x = jnp.ones((b * t, h * d), bf)
                pos = jnp.zeros((b, t), jnp.int32)
                for tile_bytes in c["sweep"]:
                    tiles["tile_bytes"] = tile_bytes
                    k_fwd, _ = on_path("kernel", (b, t, h, d), base)
                    smoke.say("P rotary %s at blocks of %d KiB: ms forward "
                              "%.3f" % (label, tile_bytes >> 10,
                                        _in_flight_ms(k_fwd, (x, pos))))
            finally:
                tiles.update(table)
    finally:
        if was is None:
            os.environ.pop("PADDLE_TPU_PALLAS", None)
        else:
            os.environ["PADDLE_TPU_PALLAS"] = was
    if failed:
        raise AssertionError("the rotary kernel is not the rule's "
                             "arithmetic: " + "; ".join(failed))


def phase_q(smoke):
    """ops/kda_kernels.py's two Pallas kernels against the same chunked form
    under lax.scan (`path="scan"`), bf16 operands as under AMP, at the
    Ling-3.0-flash cell's shape: the output and the gradients of q, k, v, g
    and beta, each by its largest error over the scan path's largest value,
    and the milliseconds of both paths, forward and forward + backward.
    The kernel path is held to the token-by-token recurrence of
    models/causal_lm_reference.py (float32, "highest") as well: the two
    chunked paths share `_prepare`, the recurrence shares nothing."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import causal_lm_reference as reference
    from paddle_tpu.ops import kda_kernels

    c = smoke.cfg["kda"]
    b, t, h, d = c["b"], c["t"], c["h"], c["d"]
    rng = np.random.RandomState(71)
    q, k, v, ct = (jnp.asarray(rng.randn(b, t, h, d), jnp.bfloat16)
                   for _ in range(4))
    beta = jax.nn.sigmoid(jnp.asarray(rng.randn(b, t, h), jnp.float32))
    a = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    rate = jnp.asarray(rng.uniform(1, 16, (h, 1)), jnp.float32)
    decays = (("a layer's start", -5.0 * jax.nn.sigmoid(rate * (a - 0.5))),
              ("every channel at the bound", jnp.full(a.shape, -5.0)))
    names = ("out", "dq", "dk", "dv", "dg", "dbeta")

    def both(fn):
        def run(*args):
            out, vjp = jax.vjp(fn, *args)
            return (out,) + vjp(ct.astype(out.dtype))
        return jax.jit(run)

    @jax.checkpoint
    def one_head(xs):       # [B, T, 1, ..]: a head's T states alone
        return reference.kda_rule(*xs)

    def recurrence(q, k, v, g, beta):
        q, k = (reference.l2norm(x.astype(jnp.float32)) for x in (q, k))
        heads = tuple(jnp.moveaxis(x, 2, 0)[:, :, :, None] for x in (
            q * d ** -0.5, k, v.astype(jnp.float32), g, beta))
        return jnp.moveaxis(jax.lax.map(one_head, heads)[:, :, :, 0], 0, 2)

    def rule(path):
        return lambda *args: kda_kernels.kda_delta_rule(
            *args, path=path, operand_dtype=jnp.bfloat16)

    failed = []
    with jax.default_device(smoke.device):
        for label, g in decays:
            args = (q, k, v, g, beta)
            runs = {path: both(rule(path)) for path in ("kernel", "scan")}
            got = {path: jax.block_until_ready(run(*args))
                   for path, run in runs.items()}
            with jax.default_matmul_precision("highest"):
                want = jax.block_until_ready(both(recurrence)(*args))
            errs = _normalized_errors(names, got["kernel"], got["scan"])
            exact = _normalized_errors(names, got["kernel"], want)
            # the decay's own gradient at the bound is e^-5 of the others'
            # and bf16's rounding of the operands does not shrink with it:
            # printed, not held to the recurrence
            held = [n for n in names if n != "dg"]
            worst = max(errs, key=errs.get)
            far = max(held, key=exact.get)
            forward = {path: jax.jit(rule(path)) for path in runs}
            smoke.say(
                "Q kda kernels, q/k/v/g [%d, %d, %d, %d] bf16 operands, %s: "
                "off the scan path by %s; off the recurrence by at most "
                "%.2e (%s; dg %.2e); ms forward kernel %.3f, scan %.3f; "
                "forward + backward kernel %.3f, scan %.3f"
                % (b, t, h, d, label,
                   ", ".join("%s %.2e" % (n, errs[n]) for n in names),
                   exact[far], far, exact["dg"],
                   _in_flight_ms(forward["kernel"], args),
                   _in_flight_ms(forward["scan"], args),
                   _in_flight_ms(runs["kernel"], args, calls=4),
                   _in_flight_ms(runs["scan"], args, calls=4)))
            if errs[worst] > c["tol"]:
                failed.append("%s: kernel against scan %s %.2e"
                              % (label, worst, errs[worst]))
            if exact[far] > c["exact_tol"]:
                failed.append("%s: kernel against the recurrence %r"
                              % (label, exact))
    if failed:
        raise AssertionError("the KDA kernels are not the chunked rule: "
                             + "; ".join(failed))


def phase_r(smoke):
    """rms_norm over a head on its two paths, the jax.numpy lines with the
    Pallas pass as their transpose (Mosaic on a TPU) and with the transpose
    jax derives (XLA), on the same bf16 x and dy and float32 weight. The
    forward pass is the lines on both (y equal); the transpose is written
    out, with a head's sums over its lanes in another order, so: dx
    differing on at most 1 element in 10,000 (3 where x is smaller than
    that, the rehearsal's), each within one bf16 unit of its head's largest
    |dx| (where g and xh * mean_D(g * xh) cancel, what is left of two
    float32 roundings is many units of a result near 0, in either path: so
    both paths' dx of the first rows are also set against the float64
    formula rounded once, and the kernel may not be off on more elements
    than XLA is, plus 1 in 10,000); dscale within 1e-5 of its largest
    element. Then both are timed, forward and forward + transpose. x comes
    and goes as [B*T, H*D] rows, as the projection beside the op writes and
    reads it."""
    import types
    import jax
    import jax.numpy as jnp
    import paddle_tpu.ops  # noqa: F401 — registers the rule
    from paddle_tpu.core import registry
    from paddle_tpu.ops import kernel_config
    from paddle_tpu.ops.nn_ops import rms_norm_path

    c = smoke.cfg["rms_head"]
    rule = registry.get("rms_norm").lower
    ctx = types.SimpleNamespace(mesh=None, amp=False)
    was = os.environ.get("PADDLE_TPU_PALLAS")
    bf = jnp.bfloat16

    def on_path(path, shape, zero_centered):
        """(forward, forward + transpose) of the rule traced on `path`:
        rows [B*T, H*D] in, rows out."""
        attrs = {"begin_norm_axis": 3, "epsilon": 1e-6,
                 "zero_centered": zero_centered}

        def norm(x, scale):
            os.environ["PADDLE_TPU_PALLAS"] = "rms_head" \
                if path == "kernel" else "0"
            x4 = x.reshape(shape)
            ins = {"X": [x4], "Scale": [scale]}
            assert rms_norm_path(ctx, x4, ins, attrs) == path
            return rule(ctx, ins, attrs)["Y"][0].reshape(x.shape)

        def both(x, scale, dy):
            y, vjp = jax.vjp(norm, x, scale)
            return (y,) + vjp(dy)
        return jax.jit(norm), jax.jit(both)

    def exact_dx(x, dy, scale, d, eps=1e-6):
        """dx of the formula in float64 from the same bf16 x and dy."""
        x, dy = (np.asarray(v.astype(jnp.float32), np.float64).reshape(
            v.shape[0], -1, d) for v in (x, dy))
        rstd = 1.0 / np.sqrt(np.square(x).mean(-1, keepdims=True) + eps)
        g, xh = dy * np.asarray(scale, np.float64), x * rstd
        dx = rstd * (g - xh * (g * xh).mean(-1, keepdims=True))
        return jnp.asarray(dx.reshape(x.shape[0], -1), jnp.float32).astype(bf)

    failed = []
    try:
        with jax.default_device(smoke.device):
            for label, b, t, h, d, zero_centered in c["cases"]:
                keys = jax.random.split(jax.random.key(72 + h), 3)
                x, dy = (jax.random.normal(k, (b * t, h * d)).astype(bf)
                         for k in keys[:2])
                scale = 0.2 * jax.random.normal(keys[2], (d,)) \
                    + (0.0 if zero_centered else 1.0)
                shape = (b, t, h, d)
                k_fwd, k_both = on_path("kernel", shape, zero_centered)
                x_fwd, x_both = on_path("xla", shape, zero_centered)
                got, want = k_both(x, scale, dy), x_both(x, scale, dy)
                counts = []
                few = max(3, x.size // 10000)
                for name, u, v in zip(("y", "dx"), got, want):
                    n, worst = _bf16_ulps(u, v)
                    counts.append("%s %d of %d differ (largest %d ulp)"
                                  % (name, n, u.size, worst))
                    if n > few or (name == "y" and worst > 1):
                        failed.append("%s %s" % (label, counts[-1]))
                # dx where its two terms cancel: against its head's largest
                dx, dx_want = (np.asarray(v.astype(jnp.float32)).reshape(
                    b * t, h, d) for v in (got[1], want[1]))
                unit = 2.0 ** (np.floor(np.log2(np.abs(dx_want).max(
                    -1, keepdims=True))) - 7)
                off_head = float((np.abs(dx - dx_want) / unit).max())
                first = min(b * t, 256)
                exact = exact_dx(x[:first], dy[:first],
                                 scale + (1.0 if zero_centered else 0.0), d)
                miss = [_bf16_ulps(v[:first], exact)[0] for v in (got[1], want[1])]
                counts.append(
                    "dx within %.2f bf16 units of its head's largest; of "
                    "the first %d rows' %d elements %d are not the float64 "
                    "formula's (xla: %d)" % (off_head, first, exact.size,
                                             miss[0], miss[1]))
                if off_head > 1 or miss[0] > miss[1] + max(
                        3, exact.size // 10000):
                    failed.append("%s %s" % (label, counts[-1]))
                ds, ds_want = (np.asarray(v, np.float64) for v in (
                    got[2], want[2]))
                off = float(np.abs(ds - ds_want).max()
                            / np.abs(ds_want).max())
                if not off <= 1e-5:
                    failed.append("%s dscale off by %.3g" % (label, off))
                once = x.size * x.dtype.itemsize
                smoke.say(
                    "R rms_norm %s %s bf16%s: %s; dscale off by %.3g; ms "
                    "forward kernel %.3f, xla %.3f; forward + transpose "
                    "kernel %.3f, xla %.3f; the bytes at 819 GB/s: forward "
                    "%.3f, transpose %.3f"
                    % (label, list(shape),
                       ", 1 + weight" if zero_centered else "",
                       "; ".join(counts), off,
                       _in_flight_ms(k_fwd, (x, scale)),
                       _in_flight_ms(x_fwd, (x, scale)),
                       _in_flight_ms(k_both, (x, scale, dy)),
                       _in_flight_ms(x_both, (x, scale, dy)),
                       1e3 * 2 * once / 819e9, 1e3 * 3 * once / 819e9))
            label, b, t, h, d, zero_centered = c["cases"][0]
            tiles = kernel_config.DEFAULT_TILES["rms_head"]
            table = dict(tiles)
            try:
                x = jnp.ones((b * t, h * d), bf)
                scale = jnp.ones((d,), jnp.float32)
                for tile_bytes in c["sweep"]:
                    tiles["tile_bytes"] = tile_bytes
                    k_fwd, k_both = on_path("kernel", (b, t, h, d),
                                            zero_centered)
                    smoke.say("R rms_norm %s at blocks of %d KiB: ms forward "
                              "%.3f, forward + transpose %.3f"
                              % (label, tile_bytes >> 10,
                                 _in_flight_ms(k_fwd, (x, scale)),
                                 _in_flight_ms(k_both, (x, scale, x))))
            finally:
                tiles.update(table)
    finally:
        if was is None:
            os.environ.pop("PADDLE_TPU_PALLAS", None)
        else:
            os.environ["PADDLE_TPU_PALLAS"] = was
    if failed:
        raise AssertionError("the rms_norm kernels are not the rule's "
                             "arithmetic: " + "; ".join(failed))


PHASES = (("A", "ResNet-50 training", phase_a),
          ("B", "transformer training", phase_b),
          ("C", "Pallas kernel families", phase_c),
          ("D", "four-chip data parallel", phase_d),
          ("E", "timing barrier", phase_e),
          ("F", "causal_conv1d kernels", phase_f),
          ("G", "looped decoder's summed gradients", phase_g),
          ("H", "LFM2's gated convolution and sigmoid router", phase_h),
          ("I", "the embedding's backward", phase_i),
          ("J", "latent attention and hyper-connections", phase_j),
          ("K", "the output head and its loss", phase_k),
          ("L", "the latent core at 192 + 64 on 256", phase_l),
          ("M", "the routed experts' grouped matmuls", phase_m),
          ("N", "the selective scan and the differential core", phase_n),
          ("O", "the state-space-dual scan", phase_o),
          ("P", "rotary_embedding's one pass", phase_p),
          ("Q", "the delta rule with a decay a channel", phase_q),
          ("R", "the norm over a head", phase_r))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal at toy sizes (needs "
                         "JAX_PLATFORMS=cpu)")
    ap.add_argument("--phases",
                    default="".join(letter for letter, _, _ in PHASES),
                    help="letters of the phases to run (default all)")
    args = ap.parse_args(argv)

    import jax
    from paddle_tpu.places import cpu_only_env
    if args.tiny:
        if not cpu_only_env():
            print("chip_smoke: --tiny is the CPU rehearsal; run it under "
                  "JAX_PLATFORMS=cpu", file=sys.stderr)
            return 2
        os.environ.update(TINY_ENV)
    from paddle_tpu.core.compile_cache import enable_persistent_cache
    cache_dir = enable_persistent_cache()
    counts = {"requests": 0, "hits": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            counts["requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            counts["hits"] += 1
    jax.monitoring.register_event_listener(on_event)

    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print("chip_smoke: jax=%s platform=%s device_kind=%r devices=%d "
          "cache_dir=%s" % (jax.__version__, dev.platform, dev.device_kind,
                            len(devices), cache_dir), flush=True)
    if dev.platform != "tpu" and not args.tiny:
        print("chip_smoke: no TPU (jax found platform %r): nothing was run"
              % dev.platform, file=sys.stderr)
        return 2

    smoke = Smoke(dev, len(devices), TINY if args.tiny else FULL, counts)
    from paddle_tpu.native import native_status
    smoke.say("native libraries: %s" % ", ".join(
        "%s=%s" % (k, "native" if v else "python fallback")
        for k, v in sorted(native_status().items())))

    results = {}
    t_all = time.perf_counter()
    for letter, title, fn in PHASES:
        if letter not in args.phases.upper():
            results[letter] = "not selected"
            continue
        t0 = time.perf_counter()
        before = dict(counts)
        try:
            skipped = fn(smoke)
        except Exception:  # noqa: BLE001 — recorded; the exit code is 1
            traceback.print_exc()
            results[letter] = "FAILED"
            skipped = None
        else:
            results[letter] = "skipped: %s" % skipped if skipped \
                else "passed"
        smoke.say("phase %s (%s) %s in %.1fs; XLA compile requests %d, "
                  "persistent-cache hits %d"
                  % (letter, title, results[letter],
                     time.perf_counter() - t0,
                     counts["requests"] - before["requests"],
                     counts["hits"] - before["hits"]))
    ok = "FAILED" not in results.values()
    smoke.say("total %.1fs; compile requests %d, persistent-cache hits %d "
              "(%s); phases %s"
              % (time.perf_counter() - t_all, counts["requests"],
                 counts["hits"], cache_dir, json.dumps(results)))
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
