"""Which kernel runs for an op, and at which tile: the one module that
decides, from what the code can observe. Read top to bottom:

  DEFAULT_TILES            the one table a block size is written in
  DEFAULT_FLASH_MIN_SEQ    the flash-or-dense query length
  dispatch_device/platform what the traced computation runs on
  pallas_explicit(op)      PADDLE_TPU_PALLAS, parsed in one place
  pallas_on(op)            the explicit setting, else "on a TPU"
  flash_min_seq()          FLAGS_flash_min_seq if set, else the constant
  flash_at(q_len)          the one flash-or-dense rule

PADDLE_TPU_PALLAS and FLAGS_flash_min_seq are how the tests and
chip_smoke.py force a path (an interpret-mode kernel on the CPU, flash
below the crossover); no model, tool or benchmark cell sets either.
Accepted forms of PADDLE_TPU_PALLAS:
    - unset/""          : every kernel on when the program dispatches
                          to a TPU, off on the CPU
    - "0"/"false"       : every pallas path off
    - "1"/"true"        : every pallas path on (interpret mode on CPU)
    - "attn,xent"       : allowlist — exactly the named ops on, the
                          rest off.  Unknown names raise LOUDLY (a typo
                          must not silently run the other path).
Op names: attn, xent, ln, lstm, seq, gdr, conv, emb, mhc, gmm, scan, ssd,
rope, kda, rms_head (KERNEL_OPS).  For 'attn' the flag is an opt-OUT only:
fused_attention's positive dispatch is always the flash_at() rule, so
enabling 'attn' does not force flash below the crossover (pin
FLAGS_flash_min_seq=0 for that).

Both variables are read at trace time, so both are part of
core.lowering.trace_env_key(), which the executors' jit caches and the
AOT compile cache key on.
"""
import os

import jax

__all__ = [
    "KERNEL_OPS", "DEFAULT_TILES", "DEFAULT_FLASH_MIN_SEQ",
    "dispatch_device", "dispatch_platform", "pallas_explicit",
    "pallas_on", "flash_min_seq", "flash_at",
]

# Every Pallas wrapper in pallas_kernels.py resolves a block argument
# left None from this table; the lowering rules pass none.  block_b=0
# means "the whole batch in one block".  "attn" is what a sweep of
# block_q in {128, 256, 512, 1024} x block_k in {128, 256, 512, 1024} on
# the v5e chose for bf16 inputs at the shapes the benchmark's cells run
# ([64, 2048, 64] unmasked and causal, [64, 4096, 128] causal; PERF.md
# section 6, PR 27): at 128 x 128 the forward kernel takes 2.9-4.1 times
# as long, dK/dV 2.2-2.6 and dQ 2.5-3.2 times, and the best pair is the
# same at D=64 and D=128, so the tile is one entry and no function of
# the shape.  "ln" is no count of rows but a budget: the bytes of the
# float32 working copy of one input tile, which
# pallas_kernels._ln_block_rows turns into rows from the N, D and dtype
# it sees (512 rows of [16384, 512] float32, 32 at D=8192).  Swept on
# the v5e over 128 KiB to 4 MiB (PERF.md section 6, PR 30): at
# [16384, 512] float32 a call takes 0.152 ms at 128 KiB, 0.110 at 256,
# 0.103 at 512 KiB, 1 MiB and 2 MiB (0.62 at the 8 rows it had), the
# same at D=2048 and D=8192; in bf16 0.068 at 512 KiB, 0.055 at 1 MiB,
# 0.054 at 2; Mosaic refuses 4 MiB of float32 tile at every width at
# its default VMEM limit.  1 MiB is the smallest budget past the knee
# at every width and dtype measured.  "conv" is the same kind of
# budget, for causal_conv_kernels.blocks: the float32 working copy of
# one [block_t, block_c] tile of x, block_c at most 512 channels.
# Swept on the v5e at [1, 4096, 8192] bf16, the Qwen3-Next cell's
# shape, forward / backward kernel alone on the host's clock (PERF.md
# section 6, PR 34; the HBM floor is 0.164 / 0.246 ms): at 512
# channels 0.509 / 0.717 ms at 128 KiB, 0.382 / 0.565 at 256, 0.314 /
# 0.485 at 512 KiB, 0.270 / 0.446 at 1 MiB, 0.243 / 0.440 at 2 MiB; at
# 1 MiB 0.293 / 0.431 with 256 channels, 0.276 / 0.564 with 1024, 0.335
# / 0.765 with 2048 (the backward pass's registers spill).  In float32
# 0.422 / 0.675 at 1 MiB, and at 2 MiB Mosaic refuses the backward
# kernel's two scratches and six buffers at its default VMEM limit, so
# 1 MiB it is.  At [2, 4096, 2048] every tile from 256 KiB up reads 0.20
# / 0.39 and XLA's own forward 0.21: the host's dispatch more than the
# kernel.  "xent" is the same kind of budget for the softmax_xent kernel
# (PR 44): 16 rows of bf16 logits a grid step at every vocabulary a cell
# has (a row of 37984 is 148 KiB in float32).  At its old 8 rows the
# kernel read float32 logits at 732 GB/s, twice the bytes it needed: AMP
# had upcast them in HBM.  On bf16 logits it takes 1.00 ms for
# [8192, 37984] in the SmallThinker cell (622 GB/s) and 2.47 for
# [16384, 50304] in OLMoE's (667 GB/s); alone, 32 and 64 rows read 5-10 %
# under 16 (2.31 / 2.29 against 2.58 ms at OLMoE's shape), not tried in a
# cell (my chip runs, PR 44: chip_smoke.py --phases K).  The 8-row
# tile of "seq" has not been swept on the chip (ROADMAP A3).  "emb" is the
# bytes of one block of vocabulary rows of embedding_grad's kernel, the
# float32 [rows, D] a grid step zero-fills, adds to and writes.  Swept
# on the v5e at 1, 2 and 4 MiB (PERF.md section 6, PR 41; the whole
# backward alone, uniform ids): 1.72 / 1.52 / 1.44 ms for 8192 rows into
# [37984, 2560], 2.61 / 2.41 / 2.32 for 16384 into [50304, 2048], 1.00 /
# 0.95 / 0.91 for 8192 into [16384, 2048], no difference at [49152,
# 2048], [18992, 2048] and [32000, 512]; two blocks in flight are half of
# Mosaic's default scoped VMEM at 4 MiB.  "mhc" is the rows of a
# stream [N, n * C] one grid step of ops/mhc_kernels.py's six stream
# kernels takes (the two coefficient kernels take 1024 tokens a step,
# a vector register a coefficient): a row of four streams of 3584 in
# bf16 is 28 KiB, so 128 rows are 3.5 MiB an operand, long enough for
# the DMA, with three such operands in two buffers each under the
# kernels' own VMEM limit.  Swept on the v5e
# (my chip run, PR 43: `chip_smoke.py --phases J`, a sub-layer's forward +
# backward alone at [4096, 14336] bf16): 2.121 ms at 64 rows, 2.094 at
# 128, 2.125 at 256: flat, the kernels run at their bytes' pace.  "gmm" is
# the rows a visit of ops/expert_gmm.py's three kernels streams past an
# expert's resident matrix (inside it they go to the MXU 128 at a time).
# Swept on the v5e (my chip runs, PR 50: `chip_smoke.py --phases M`, a
# layer's nine matmuls alone at the six expert cells' shapes, ms): 256 /
# 512 / 1024 rows read 4.25 / 4.21 / 4.20 at SmallThinker's shape, 3.95 /
# 3.89 / 3.85 at LFM2's, 25.51 / 25.19 / 25.49 at OLMoE's, 2.06 / 2.05 /
# 2.03 at Qwen3-Next's, 2.08 / 2.02 / 2.02 at Xing4.0's, 2.68 / 2.62 / 2.57 at
# GLM-4.7-Flash's: flat, so one entry and no rule on the shape.  Inner
# trips of 256 rows were 2-5 % slower than 128 at every shape and whole
# tiles of 512 8-20 %: a trip that starts in another group's rows is work
# for nothing, and a taller dot bought nothing back where a group fills it.
# "ssd" is the chunk of ops/ssd_kernels.py's two passes (the tokens a
# grid step computes as matmuls; Mosaic wants whole 128s) and the heads a
# grid step takes, 128 lanes (two heads of 64) at a time.  Swept on the v5e
# (my chip runs, PR 57: `chip_smoke.py --phases O`, one layer's scan alone
# at [1, 2048, 64, 64] on 128 states, forward / forward + backward ms, the
# final kernels): (128, 8) 0.28 / 1.23, (128, 16) 0.28 / 1.09, (128, 32)
# 0.28 / 1.04, (256, 16) 0.28 / 1.07: the forward is flat (the
# bytes and the steps' overhead, not the products), the backward wants
# fewer, larger steps and no longer chunk (the products under L grow with
# it).  32 heads a step is 5 % under 16 on the backward pass alone and
# twice the unrolled body to trace and compile, so 16.  "rope" is the
# bytes a grid step of ops/rotary_kernels.py's one pass reads: one block
# of rows of x [B*T, H*D], in x's dtype, and with it one block of each of
# the two float32 tables, 128 lanes a row (at one head four times x's
# bytes; the step writes a block of x's size besides).  The rows of a
# block are the most whole sublane tiles inside it that DIVIDE B*T
# (rotary_kernels.block_rows says why no block is ragged).  Swept on the
# v5e (my chip run, PR 70: `chip_smoke.py --phases P`, the forward pass
# alone at SDAR's q, [8192, 4096] bf16, whose bytes take 0.174 ms at 819
# GB/s): 0.308 ms at 512 KiB (blocks of 32 rows), 0.297 at 1 MiB (64),
# 0.299 at 2 MiB (128), in flight from the host, which reads 0.20 ms for
# a call of 8 MiB: in the cell's trace a q call is 0.18 ms.  Flat: the
# bytes' pace.  At 2 MiB x's and the tables' blocks, each held twice by
# Mosaic, and the result's stay under 8 MiB of its 16 MiB of VMEM.  "kda"
# (ops/kda_kernels.py) starts from "gdr"'s pair, whose kernels its own are
# with the state transposed: not swept yet (PERF.md section 7, PR 71).
# "rms_head" is the bytes of one block of rows of a group of heads of x
# [B*T, H*D], in x's dtype, that a grid step of ops/rms_norm_kernels.py's
# pass reads (a block of dy beside it, and it writes one of dx; no table).
# The rows are rotary_kernels.block_rows' at no table: whole sublane tiles
# that DIVIDE B*T.  Swept on the v5e (my chip run, PR 72: `chip_smoke.py
# --phases R`, SDAR's q [8192, 4096] bf16, the lines forward + the kernel
# in flight from the host, ms; see PERF.md section 6): flat from 512 KiB
# to 2 MiB, the lane sums' pace and not the blocks'.  At 1 MiB the three
# blocks, two buffers each, are 6 MiB of Mosaic's 16.
DEFAULT_TILES = {
    "attn": {"block_q": 512, "block_k": 512},
    "xent": {"tile_bytes": 1 << 20},
    "ln": {"tile_bytes": 1 << 20},
    "lstm": {"block_b": 0},
    "seq": {"block_n": 8},
    "gdr": {"chunk": 64, "block_h": 8},
    "conv": {"tile_bytes": 1 << 20},
    "emb": {"tile_bytes": 4 << 20},
    "mhc": {"block_rows": 128},
    "gmm": {"block_m": 512},
    "scan": {"chunk": 64},
    "ssd": {"chunk": 128, "block_h": 16},
    "rope": {"tile_bytes": 2 << 20},
    "kda": {"chunk": 64, "block_h": 8},
    "rms_head": {"tile_bytes": 1 << 20},
}
KERNEL_OPS = frozenset(DEFAULT_TILES)
# Dense attention below this query length, flash at and above it.  The
# cells sit on both sides (T=256 dense; T=2048 and T=4096 flash).  The
# value predates PR 27's kernels, which are 3-4 times faster, and has
# not been measured against them (ROADMAP A1 (c)).
DEFAULT_FLASH_MIN_SEQ = 1024


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
    kernel applies when nothing is set."""
    return dispatch_device().platform


def pallas_on(op):
    """Is the pallas fast path enabled for `op`?  The explicit flag
    wins; with nothing set a kernel runs exactly when dispatching to a
    real TPU (an interpret-mode kernel on the CPU is a test path, not a
    default).  `fused_attention` does not ask here: its rule is
    flash_at()."""
    explicit = pallas_explicit(op)
    if explicit is not None:
        return explicit
    return dispatch_platform() == "tpu"


def flash_min_seq():
    """The flash-or-dense query length: FLAGS_flash_min_seq when set (0
    forces flash at every length above 1), else
    DEFAULT_FLASH_MIN_SEQ."""
    env = os.environ.get("FLAGS_flash_min_seq", "")
    if env:
        try:
            return int(env)
        except ValueError:
            return DEFAULT_FLASH_MIN_SEQ
    return DEFAULT_FLASH_MIN_SEQ


def flash_at(q_len):
    """The one flash-or-dense decision for fused_attention at query
    length `q_len` (the traced q.shape[1]; None when symbolic).

      * q_len <= 1 (one query row a step, the decode-serving shape) ->
        dense, even under FLAGS_flash_min_seq=0: a block_q-row tile for
        a 1-row query is wrong by construction, not a matter of where
        the crossover sits;
      * PADDLE_TPU_PALLAS opts 'attn' out (=0, or an allowlist without
        it) -> dense at every length;
      * q_len is None -> flash (the crossover cannot be evaluated on a
        symbolic length);
      * otherwise flash exactly when q_len >= flash_min_seq().

    The platform is not asked: off a TPU the flash kernel runs in
    interpret mode, and the dispatch stays one function of the shape."""
    if q_len is not None and q_len <= 1:
        return False
    if pallas_explicit("attn") is False:
        return False
    if q_len is None:
        return True
    return q_len >= flash_min_seq()
