"""Lowerings of the linear-attention mixer's ops: the gated delta rule
(ops/gated_delta_kernels.py), the delta rule with a decay a key channel
(ops/kda_kernels.py), the short causal depthwise convolution
over time that feeds it (ops/causal_conv_kernels.py), a Mamba mixer's
selective scan (ops/selective_scan_kernels.py) and a Mamba-2 mixer's
state-space-dual scan (ops/ssd_kernels.py). No reference-era op computes
any of them: sequence_conv is LoD-based and dense over channels."""
import jax
import jax.numpy as jnp

from ..core.registry import counts, register, shapes_from, single
from ..observability.registry import REGISTRY
from .kernel_config import DEFAULT_TILES, pallas_on


def gated_delta_path():
    """"kernel" where the gated delta rule's Pallas kernels are on
    (kernel_config.pallas_on("gdr"): a TPU, or PADDLE_TPU_PALLAS), else
    "scan". The one place that decides; the layer counter reads it too."""
    return "kernel" if pallas_on("gdr") else "scan"


@register("gated_delta_rule", calls_pallas=True, infer=shapes_from(Out="V"))
def _gated_delta_rule(ctx, ins, attrs):
    """Out [B, T, Hv, dv] of the gated delta rule for Q, K [B, T, Hk, dk],
    V [B, T, Hv, dv], G (log decay) and Beta [B, T, Hv]. Under AMP the
    matmuls take bf16 operands; G, Beta, the running sums, exponentials and
    the state are float32 either way, so the op is in neither AMP table."""
    from .gated_delta_kernels import gated_delta_rule
    out = gated_delta_rule(
        *(single(ins, name) for name in ("Q", "K", "V", "G", "Beta")),
        operand_dtype=jnp.bfloat16 if getattr(ctx, "amp", False) else None,
        path=gated_delta_path())
    return {"Out": [out]}


def _count_linear_attention_layer(kind, tile, path, ins, **own):
    """The one counter of the two delta rules: each registers beside its
    rule and says its kind, its DEFAULT_TILES row, its path and its own
    labels."""
    k, v = ins["K"][0], ins["V"][0]
    REGISTRY.counter(
        "ptpu_linear_attention_layers_total",
        "linear-attention ops lowered (forward ops, not a grad op's replay), "
        "by kind (gated_delta: a decay a head; kda: a decay a key channel), "
        "key and value heads and their widths, the chunk and the path of the "
        "pass over chunks (the Pallas kernels, or lax.scan); and, for kda "
        "alone, sub_block: the rows that share one reference for the "
        "exponentials of the decayed products"
    ).inc(kind=kind, k_heads=str(k.shape[2]), v_heads=str(v.shape[2]),
          d_k=str(k.shape[3]), d_v=str(v.shape[3]),
          chunk=str(DEFAULT_TILES[tile]["chunk"]), path=path, **own)


@counts("gated_delta_rule")
def _count_gated_delta_layer(ctx, attrs, ins):
    _count_linear_attention_layer("gated_delta", "gdr", gated_delta_path(),
                                  ins)


def kda_path():
    """"kernel" where the channel-decay delta rule's Pallas kernels are on
    (kernel_config.pallas_on("kda"): a TPU, or PADDLE_TPU_PALLAS), else
    "scan". The one place that decides; the layer counter reads it too."""
    return "kernel" if pallas_on("kda") else "scan"


@register("kda_delta_rule", calls_pallas=True, infer=shapes_from(Out="V"))
def _kda_delta_rule(ctx, ins, attrs):
    """Out [B, T, H, dv] of the delta rule whose decay is a key channel's
    (Kimi Delta Attention) for Q, K [B, T, H, dk], V [B, T, H, dv], G [B, T,
    H, dk] (log decay, above -5.9 a token) and Beta [B, T, H]. Under AMP the
    matmuls take bf16 operands; G, Beta, the running sums, exponentials and
    the state are float32 either way, so the op is in neither AMP table."""
    from .kda_kernels import kda_delta_rule
    out = kda_delta_rule(
        *(single(ins, name) for name in ("Q", "K", "V", "G", "Beta")),
        operand_dtype=jnp.bfloat16 if getattr(ctx, "amp", False) else None,
        path=kda_path())
    return {"Out": [out]}


@counts("kda_delta_rule")
def _count_kda_layer(ctx, attrs, ins):
    from .kda_kernels import SUB_BLOCK
    _count_linear_attention_layer("kda", "kda", kda_path(), ins,
                                  sub_block=str(SUB_BLOCK))


def causal_conv_path(x, w):
    """"kernel" where causal_conv_kernels' two streaming passes run for X
    [B, T, C] under Filter [C, K]: kernel_config.pallas_on("conv") (a TPU,
    or PADDLE_TPU_PALLAS) and blocks that divide the shape (T a multiple of
    16, C of 128); else "xla", the jax.numpy passes below. The one place
    that decides; the layer counter reads it too."""
    from .causal_conv_kernels import applies
    fits = x.ndim == 3 and applies(x.shape[1], x.shape[2], w.shape[1])
    return "kernel" if fits and pallas_on("conv") else "xla"


@register("causal_conv1d", calls_pallas=True, infer=shapes_from(Out="X"))
def _causal_conv1d(ctx, ins, attrs):
    """y_t[c] = sum_m w[c, m] x_(t-K+1+m)[c] over X [B, T, C] with Filter
    [C, K], zeros before the sequence, then `activation` ("silu" or none):
    K shifted multiply-adds in float32, back in x's dtype. As one Pallas
    pass forward and one backward where `causal_conv_path` says so. Else
    K passes over a padded float32 copy, under jax.checkpoint: the backward
    pass keeps x as it came (bf16 under AMP) and converts and pads it
    again, where XLA would hold the float32 padded copy from the forward
    pass (256 MiB a layer at [2, 4096, 8192]; AOT compile, PR 33)."""
    silu = attrs.get("activation") == "silu"
    x, w = single(ins, "X"), single(ins, "Filter")
    if causal_conv_path(x, w) == "kernel":
        from .causal_conv_kernels import causal_conv1d
        return {"Out": [causal_conv1d(x, w, silu=silu)]}

    @jax.checkpoint
    def conv(x, w):
        width, t = w.shape[1], x.shape[1]
        xp = jnp.pad(x.astype(jnp.float32), [(0, 0), (width - 1, 0), (0, 0)])
        w = w.astype(jnp.float32)
        y = sum(xp[:, m:m + t] * w[:, m] for m in range(width))
        return (jax.nn.silu(y) if silu else y).astype(x.dtype)

    return {"Out": [conv(x, w)]}


@counts("causal_conv1d")
def _count_causal_conv_layer(ctx, attrs, ins):
    x, w = ins["X"][0], ins["Filter"][0]
    REGISTRY.counter(
        "ptpu_causal_conv_layers_total",
        "causal_conv1d ops lowered (forward ops, not a grad op's replay), by "
        "the path taken (the two Pallas kernels, or XLA's shifted passes), "
        "the filter's taps, the channels and the activation"
    ).inc(path=causal_conv_path(x, w), width=str(w.shape[1]),
          channels=str(w.shape[0]),
          activation=str(attrs.get("activation", "none")))


def selective_scan_path(x, a):
    """"kernel" where selective_scan_kernels' two passes run for X [B, T,
    C] under A [C, N]: kernel_config.pallas_on("scan") (a TPU, or
    PADDLE_TPU_PALLAS) and whole registers of channels at no more than 16
    states; else "xla", lax.scan over tokens. The one place that decides;
    the layer counter reads it too."""
    from .selective_scan_kernels import applies
    fits = x.ndim == 3 and applies(x.shape[2], a.shape[1])
    return "kernel" if fits and pallas_on("scan") else "xla"


@register("selective_scan", calls_pallas=True, infer=shapes_from(Out="X"))
def _selective_scan(ctx, ins, attrs):
    """Out [B, T, C] of the selective scan s_t = exp(Delta_t A) s_(t-1) +
    Delta_t B_t x_t, y_t = C_t . s_t + D x_t for X, Delta [B, T, C], A [C,
    N] (negative), B, C [B, T, N] and D [C]: every product, exponential and
    the state in float32 whatever the operands come in (the op is in neither
    AMP table), the result back in X's dtype."""
    from .selective_scan_kernels import selective_scan
    x, delta, a, b, c, d = (single(ins, name) for name in (
        "X", "Delta", "A", "B", "C", "D"))
    out = selective_scan(x, delta, a, b, c, d, path=selective_scan_path(x, a))
    return {"Out": [out.astype(x.dtype)]}


@counts("selective_scan")
def _count_selective_scan_layer(ctx, attrs, ins):
    x, a = ins["X"][0], ins["A"][0]
    REGISTRY.counter(
        "ptpu_selective_scan_layers_total",
        "selective_scan ops lowered (forward ops, not a grad op's replay), "
        "by the channels, the states a channel, the tokens between two "
        "states the backward pass is given and the path (the two Pallas "
        "kernels, or lax.scan over tokens)"
    ).inc(channels=str(a.shape[0]), states=str(a.shape[1]),
          chunk=str(DEFAULT_TILES["scan"]["chunk"]),
          path=selective_scan_path(x, a))


def ssd_scan_path(x, groups=1):
    """"kernel" where ssd_kernels' two passes over chunks run for X [B, T,
    H, P]: kernel_config.pallas_on("ssd") (a TPU, or PADDLE_TPU_PALLAS) and
    heads that fill whole lane tiles (P divides 128), a group's where B and
    C come in `groups`; else "scan", the same chunked form in jax.numpy
    under lax.scan. The one place that decides; the layer counter reads it
    too."""
    from .ssd_kernels import applies
    fits = x.ndim == 4 and x.shape[2] % groups == 0 \
        and applies(x.shape[2] // groups, x.shape[3])
    return "kernel" if fits and pallas_on("ssd") else "scan"


@register("ssd_scan", calls_pallas=True, infer=shapes_from(Out="X"))
def _ssd_scan(ctx, ins, attrs):
    """Out [B, T, H, P] of the state-space-dual scan s_t = exp(Delta_t A)
    s_(t-1) + B_t^T (Delta_t x_t), y_t = C_t s_t + D x_t a head, for X [B,
    T, H, P], Delta [B, T, H], A (negative) and D [H], and B, C [B, T, N]
    that all heads read, or [B, T, G, N], head h reading group h // (H /
    G). Under AMP the matmuls take bf16 operands; Delta,
    A, the running sums, every exponential, the state and every accumulator
    are float32 either way, so the op is in neither AMP table. The result
    comes back in X's dtype."""
    from .ssd_kernels import ssd_scan
    x, delta, a, b, c, d = (single(ins, name) for name in (
        "X", "Delta", "A", "B", "C", "D"))
    out = ssd_scan(
        x, delta, a, b, c, d,
        path=ssd_scan_path(x, b.shape[2] if b.ndim == 4 else 1),
        operand_dtype=jnp.bfloat16 if getattr(ctx, "amp", False) else None)
    return {"Out": [out.astype(x.dtype)]}


@counts("ssd_scan")
def _count_ssd_scan_layer(ctx, attrs, ins):
    x, b = ins["X"][0], ins["B"][0]
    # B and C [B, T, G, N] say their groups; one group that all heads read
    # counts under the labels it always had
    groups = b.shape[2] if b.ndim == 4 else 1
    REGISTRY.counter(
        "ptpu_ssd_scan_layers_total",
        "ssd_scan ops lowered (forward ops, not a grad op's replay), by the "
        "heads (those held, where a share is), a head's channels, the "
        "states a channel, the chunk, the path of the pass over chunks (the "
        "two Pallas kernels, or lax.scan) and, where B and C come in more "
        "than one, the groups"
    ).inc(heads=str(x.shape[2]), head_dim=str(x.shape[3]),
          states=str(b.shape[-1]), chunk=str(DEFAULT_TILES["ssd"]["chunk"]),
          path=ssd_scan_path(x, groups),
          **({"groups": str(groups)} if b.ndim == 4 else {}))
