"""Decoder-only language model built from fluid layers, driven by a config
dict whose keys are those of a Hugging Face `config.json`.

One builder for the family: token embedding (no scale, no position table),
N x (a norm, a mixer, a norm, by layer a dense SwiGLU FFN or dropless
top-k routed experts with an optional gated shared expert beside them),
final RMS norm, an output head of its own or tied to the embedding,
next-token cross-entropy plus the routers' auxiliary losses. The mixer is,
by layer, self-attention (grouped queries,
optional QK-norm over all channels or a head, rotary positions on the whole
head or its first channels on the layers whose pattern says so, a sliding
window on the layers whose pattern says so, an optional sigmoid gate on the
output) or a gated delta net (a causal depthwise convolution over q, k and
v, the gated delta rule, a gated RMS norm a head) or a gated short
convolution (C * conv(B * u) between two projections) or a Mamba mixer (a
selective scan behind a convolution) or a gated memory unit on another
layer's scan output or a Mamba-2 mixer (a state-space-dual scan behind a
convolution, gated before its norm); or a layer is ONE branch, a mixer or
an FFN alone, by `hybrid_override_pattern`. A new decoder-only
architecture is a config plus the ops it lacks, not a model file. Users:
OLMoE-1B-7B (`model_type: olmoe`; Muennighoff et al. 2024, arXiv:2409.02060),
SmallThinker-21BA3B (PowerInfer; window and full attention mixed with
period 4, no rotary on the full layers, the router read from the attention's
normed input, ReGLU experts) and Qwen3-Next-80B-A3B (`model_type:
qwen3_next`; three gated delta nets to one gated full-attention layer,
zero-centred norm weights, 512 experts beside a gated shared one) and
Ouro-2.6B (`model_type: ouro`; Zhu et al. 2025, arXiv:2510.25741: one stack
of dense layers run total_ut_steps times over the same weights, every
branch normed going in and coming out, an exit gate that weighs the passes'
losses) and LFM2-8B-A1B (`model_type: lfm2_moe`, LiquidAI: three gated
short convolutions to one attention layer with QK-norm a head, leading
dense layers before the expert layers, a sigmoid router whose top-k is
chosen with an expert bias and weighed without it, a tied head) and
Xing4.0-29B-A4B (`model_type: xing4_0`, XingChen-AGI: DeepSeek-V3's latent
attention, sigmoid router with a correction bias and ungated shared expert
after leading dense layers, behind hc_mult residual streams that
manifold-constrained hyper-connections mix, arXiv:2512.24880), whose
equations the module follows; `causal_lm_reference.py` is the same forward
in plain jax.numpy.

Config keys read (HF names): vocab_size, hidden_size, num_hidden_layers,
num_attention_heads, num_key_value_heads (a divisor of the heads: query
head h reads key/value head h // group), head_dim (absent: hidden_size /
heads), intermediate_size (the dense FFN's width, or one expert's),
num_experts (0 or absent: dense SwiGLU), num_experts_per_tok,
norm_topk_prob, rms_norm_eps, rope_theta (None: no rotary), hidden_act
(silu; relu for experts), attention_bias (false), clip_qkv (null),
rope_scaling (null, or type yarn: below), tie_word_embeddings (false; true:
LFM2's, below),
initializer_range, embedding_initializer_range (absent: the same),
router_aux_loss_coef, router_z_loss_coef; `qk_norm`, which `config.json`
does not carry because `modeling_olmoe.py` always applies it (true: over all
channels before the head split; "head": over each head's own); and
`router_input` ("own", or "pre_attention": the router reads the
attention's normed input). Qwen3-Next's: full_attention_interval (layer i is
full attention where (i + 1) % interval == 0, else a gated delta net),
linear_num_key_heads, linear_num_value_heads, linear_key_head_dim,
linear_value_head_dim, linear_conv_kernel_dim, partial_rotary_factor (the
share of a head's channels that rotary turns), moe_intermediate_size (an
expert's width), shared_expert_intermediate_size, mlp_only_layers (empty),
decoder_sparse_step (1); and, as keys of their own like `qk_norm`, what
`modeling_qwen3_next.py` always applies and `config.json` therefore does
not carry: `norm_zero_centered` (a norm's weight is stored around 0,
(1 + w) * x_hat) and `attention_gate` (the query projection is twice as
wide, a head [q, gate], and ctx * sigmoid(gate) enters Wo). Ouro's:
total_ut_steps (the passes over the stack; 1: no loop) and
early_exit_threshold (1: every pass runs, the only value built); and, as
keys of their own, what `modeling_ouro.py` always does: `sandwich_norm` (a
layer is a = x + N2(mixer(N1(x))), a + N4(FFN(N3(a)))) and `exit_gate`
(lambda_t = sigmoid(h_t w_g + b_g) on every pass's normed state; the loss
is the exit distribution's expected cross-entropy less `exit_entropy_coef`
x its entropy). LFM2's: layer_types (one "conv" or "full_attention" a
layer: a short_conv mixer or attention; a list longer than the stack is
cut to it only where it is one kind throughout; absent:
full_attention_interval decides), conv_L_cache (the short convolution's
taps), conv_bias (false), num_dense_layers (the first so many layers have
a dense FFN at intermediate_size, the others experts at
moe_intermediate_size; 0),
use_expert_bias (false), routed_scaling_factor (1), norm_eps (an alias of
rms_norm_eps), tie_word_embeddings true (the head reads the embedding's
parameter, transposed); and, as keys of their own, what
`modeling_lfm2_moe.py` always does: `router_scoring` ("softmax", or
"sigmoid": s = sigmoid(logits), the top k chosen over s + the expert bias,
weighed by s, renormalised over the chosen with 1e-6 added to their sum;
router_aux_loss_coef and router_z_loss_coef must then be 0, and
use_expert_bias needs it) and `expert_bias_initializer_range` (the bias is
held, not trained: a parameter without gradient or optimizer state, drawn
normal(0, that) at startup from a stream of its own, EXPERT_BIAS_SEED, the
same draw in every run as a checkpoint's buffer is the same under whatever
else is initialised around it; 0: zeros). A loop over short_conv mixers is
refused. The final norm closes every pass, and the next pass starts from
the normed state. A loop over routed experts is refused. Xing4.0's
(DeepSeek-V3's names): q_lora_rank, kv_lora_rank, qk_nope_head_dim,
qk_rope_head_dim, v_head_dim (all five or none: latent attention, c_q = N(x
W_qa), q = c_q W_qb a head [nope; rope]; [c_kv; k_r] = x W_kva, kv = N(c_kv)
W_kvb a head [k_nope; v]; rotary on q's rope part and on k_r, one key all
heads read; a key/value head a query head, no QK-norm, no gate);
rope_scaling {type yarn, factor, original_max_position_embeddings,
beta_fast (32), beta_slow (1), mscale (1), mscale_all_dim (0)}: the
frequency table and the scores' scale of `yarn_table`, made on the host
once a program, for either attention form (any other type is refused);
n_routed_experts (num_experts), first_k_dense_replace (num_dense_layers),
scoring_func (router_scoring), topk_method (noaux_tc: use_expert_bias;
greedy: none; others refused), n_shared_experts (so many SwiGLUs of
moe_intermediate_size every token passes, as one of their summed width,
ungated: shared_expert_gate false, where Qwen3-Next's is gated), n_group
and topk_group (1 and 1: no limit; n_group > topk_group under noaux_tc:
the group-limited choice, below), moe_layer_freq (1),
num_nextn_predict_layers (0, or 1: one multi-token-prediction module,
below; more are refused), ep_size and max_position_embeddings (not
read); hc_mult (the residual streams n; 1: the plain residual), where n > 1
hc_sinkhorn_iters, hc_eps, mhc_h_res_clamp_min and mhc_h_res_clamp_max (the
hyper-connections': layers.mhc_pre has the equations); a looped stack over
hc_mult > 1 is refused. As keys of their own, what the published files do
not fix: `rope_interleaved` (rotary pairs (2i, 2i + 1), DeepSeek-V3's
stored layout; false: half-split), `router_renorm_epsilon` (what a sigmoid
router's renormalisation adds to the chosen scores' sum; null: LFM2's 1e-6;
DeepSeek-V3's 1e-20), `hc_alpha_init` and `hc_res_diag_init` (the
hyper-connections' start, `hyper_connection`: 0.5 and 1.0 unless given, a
model whose coefficients differ from token to token; arXiv:2512.24880
starts at alpha 0.01 with H_res near the identity). `mtp_loss_weight`
(lambda, the weight of the module's loss term; 0.3, DeepSeek-V3's for most
of its pre-training). `model_type`
is not read: a config says what it builds by these keys. SmallThinker's own
names are mapped onto these:
moe_ffn_hidden_size (intermediate_size), moe_num_primary_experts
(num_experts), moe_num_active_primary_experts (num_experts_per_tok),
moe_primary_router_apply_softmax (true), rope_layout and
sliding_window_layout (one 0/1 a layer: rotary on, window on) and
sliding_window_size.

Phi-4-mini-flash-reasoning's (`model_type: phi4flash`; SambaY's
decoder-hybrid-decoder, arXiv:2507.06607): mb_per_layer (a Mamba mixer on
the layers whose published index is a multiple of it; `_decoder_hybrid_
decoder` has the pattern: Mamba and windowed attention below the middle, the
middle layer's scan output and the next layer's keys and values handed on,
gated memory units and attention with a query projection alone above),
sliding_window, layer_norm_eps (LayerNorm with weight and bias as the
block's norm, for rms_norm_eps), embd_pdrop and resid_pdrop (0), mlp_bias
and lm_head_bias (false), no rope_theta (no positional term: absent under
mb_per_layer is None); and, as keys of their own, what
`modeling_phi4flash.py` always does and `config.json` does not carry:
`differential_attention` (arXiv:2410.05258: `differential_attention` has the
equations), `attention_bias` and `conv_bias` (true: built under these two
mixers alone), `mlp_gate_up_fused` (the MLP's first matrix holds gate and
value), `mamba_d_state` (16), `mamba_d_conv` (4), `mamba_expand` (2),
`mamba_dt_rank` ("auto": hidden_size / 16 rounded up),
`lambda_initializer_range` (0.1) and `layer_indices` (the published index
of each layer a cut stack kept; a layer's kind, window and lambda_init
follow it).
granite-4.0-h-micro's (`model_type: granitemoehybrid`; Mamba-2,
arXiv:2405.21060): layer_types of `mamba` (a Mamba-2 mixer, "mamba2" among
`mixer_layers`, known where the config has mamba_n_heads: `mamba2` has the
equations) and `attention`; a list longer than the stack is cut to its
first layers where share.published.num_hidden_layers says it is the whole
model's; mamba_n_heads x mamba_d_head = mamba_expand x hidden_size,
mamba_d_state, mamba_d_conv, mamba_conv_bias (true), mamba_proj_bias (false;
true refused), mamba_n_groups (1; more: nemotron_h's, below),
mamba_chunk_size (not
read: the kernels' chunk is kernel_config's); position_embedding_type (rope,
or nope: no rotary in any layer, whatever rope_theta says);
shared_intermediate_size (the dense MLP's width, its first matrix gate and
value in one), num_local_experts 0 (dense; routed ones refused under this
key); and four scalars, each 1 by default and an op only where it is not:
embedding_multiplier (on the embedding's output), residual_multiplier (on
what each of a layer's two branches adds to the stream),
attention_multiplier (the scores' scale ITSELF, what the attention's core
is given, in place of head_dim^-0.5), logits_scaling (the logits are
divided by it). A Mamba-2 layer's parameters: w_in, conv, [conv.bias],
dt_bias, a_log, d, gated_norm, w_out.
NVIDIA-Nemotron-3-Super's (`model_type: nemotron_h`): hybrid_override_pattern,
a letter a layer, and a layer is ONE branch behind one norm, h + f(N(h)):
`M` a Mamba-2 mixer, `*` attention, `E` routed experts, `-` a dense MLP
(`_one_branch_layers`; a pattern longer than the stack is cut as a
layer_types list is; other letters are refused). The family's names are
mapped (NEMOTRON_H): mamba_num_heads, mamba_head_dim, ssm_state_size,
conv_kernel, n_groups (mamba_n_groups: G groups of B and C, head h reads
group h // (H / G), and the gated norm is a norm a group under one weight;
G must divide the heads), expand, use_conv_bias, layer_norm_epsilon (an RMS
norm's), mlp_hidden_act (relu2, the only value built under the pattern:
every FFN there is UNGATED, relu(x W_up)^2 W_down, the routed experts two
matrices an expert), moe_shared_expert_intermediate_size; moe_latent_size (L
> 0: the experts work on u = x W_dn [L] and their sum goes through W_up [L,
D], while the router and the shared expert read x; refused outside the
pattern); mtp_hybrid_override_pattern (`*E`, the module's one layer with
both branches, the only value built); use_bias (false). What the family's
modeling file always does and its config.json does not carry is a default
there that a config's own key overrides (NEMOTRON_H_ALWAYS): router_scoring
sigmoid with use_expert_bias, router_renorm_epsilon 1e-20, no auxiliary
loss, shared_expert_gate false; rope_theta is not read (no positional
term). chunk_size, time_step_min/max/floor, rescale_prenorm_residual,
residual_in_fp32, moe_shared_expert_overlap, num_logits_to_keep and
use_mamba_kernels are not read. A one-branch layer's parameters: `M`: norm,
w_in, conv, [conv.bias], dt_bias, a_log, d, gated_norm, w_out; `*`: norm,
wq, wk, wv, wo; `E`: norm, [latent_down], experts.router,
[experts.expert_bias], experts.w_up, experts.w_down, [latent_up],
shared_expert.w_up, shared_expert.w_down; `-`: norm, w_up, w_down.
Laguna-S-2.1's (`model_type: laguna`, poolside): attention whose geometry
is a LAYER's (`_geometry_by_layer`, known where the config has
rope_parameters or num_attention_heads_per_layer): layer_types of
`full_attention` and `sliding_attention` (an attention layer behind the
window `sliding_window`: key j is visible to query i iff 0 <= i - j <
sliding_window); num_attention_heads_per_layer (layer i has H_i query heads
on the model's num_key_value_heads, every H_i a multiple of them; W_q, W_g
and W_o are [D, H_i x head_dim], so head_dim must be given and is not held
to hidden_size / heads; a list longer than the stack is cut to its first
layers where it is the share's published num_hidden_layers of them);
rope_parameters, a set a KIND of layer, {rope_type default or yarn (others
refused), rope_theta, partial_rotary_factor, and for yarn factor,
original_max_position_embeddings, beta_fast, beta_slow and attention_factor
(the factor on cos and sin; absent: `yarn_table`'s m(1) / m(0) = 0.1
ln(factor) + 1)}: ONE table a kind, made once, read by every layer of the
kind (`rope_tables`, counted by ptpu_rope_tables_total), the scores' scale
head_dim^-1/2 unless the set has mscale_all_dim; gating `per-head`
(attention_gate "per_head": g = sigmoid(x W_g) [T, H_i], a projection of its
own without bias, `layer_<i>.wg`, one scalar a head on the core's output
before W_o: the headwise form of arXiv:2505.06708; other values refused);
mlp_only_layers as a LEADING run (0 .. n - 1: num_dense_layers n; any other
list refused); moe_routed_scaling_factor (routed_scaling_factor);
mlp_layer_types and gating_types, which say again what mlp_only_layers and
gating say and are held to them, not read twice;
moe_apply_router_weight_on_input (false) and moe_router_logit_softcapping
(0): other values refused. The softmax router, the gated shared expert and
the QK-norm a head (`qk_norm: head`, a key of the config's own) are
Qwen3-Next's. A share's published num_attention_heads_per_layer is held to
the heads given: each is the published count x held / published key/value
heads. A layer's attention parameters: wq, wk, wv, [q_norm, k_norm], wg, wo.
SDAR-30B-A3B-Chat's (`model_type: sdar_moe`, JetLM; SDAR, arXiv:2510.06303):
the layer is Qwen3-MoE's, by keys the builder had (num_experts,
moe_intermediate_size, norm_topk_prob, head_dim, num_key_value_heads,
decoder_sparse_step 1, mlp_only_layers empty; `qk_norm: head` a key of the
config's own), and use_sliding_window (false: true refused), sliding_window
(null) and max_window_layers (not read). What the family adds is its
TRAINING OBJECTIVE, one block of the config: `objective: block_diffusion`
(BD3-LM, arXiv:2503.09573; absent, the objective is next-token as it always
was, and no program changes), `block_length` (positions a block),
`mask_token_id` (among the held words) and `noise_eps` (the batch's, not
read by the program). Under it the feeds are `ids` [B, T] the clean ids,
`noisy_ids` [B, T] (the mask id where a position was masked), `pos` [B, T]
and `loss_weight` [B, T] float32 (1 / t at a masked position, 0 elsewhere),
and no `labels`; the rows are E[noisy_ids] then E[ids], 2 T of them from ONE
lookup of ONE embedding parameter, both copies of token i at rotary position
pos[i]; every layer's attention runs under the block-diffusion mask
(`layers.fused_attention(block_diffusion=(block_length, T))`: row r = (copy,
position i, block b = i // block_length) sees row s iff both are noised and
b_s = b_r, or r is noised, s clean and b_s < b_r, or both are clean and b_s
<= b_r); behind the LAST layer's core the noised copy's rows alone go on (W_o,
the residual, the FFN, the final norm and the head on T rows: nothing reads a
clean row there); the loss is the mean over the B x T noised rows of
loss_weight_i x CE(logits_i, ids_i), the label the clean id at the SAME
position, a sequence's sum divided by its T tokens. `resolve` refuses under
the objective, by name: a window, a looped stack, several streams, a
multi-token-prediction module, a mixer other than attention, latent or
differential attention, a tied head, a mask_token_id outside the held words;
`causal_lm` a block_length that does not divide the sequence. Parameters in
the order and under the names of any attention-and-experts layer:
embedding; layer_<i>.input_norm, wq, wk, wv, q_norm, k_norm, wo,
post_attention_norm, experts.router, experts.w_gate, experts.w_up,
experts.w_down; final_norm; head.
Ling-3.0-flash's (`model_type: bailing_hybrid`, inclusionAI; Kimi Delta
Attention, Kimi Linear, arXiv:2510.26692), known where the config has
layer_group_size (`_bailing_hybrid`): layer_group_size (published layer i
is latent attention where (i + 1) % it == 0, else a KDA mixer, "kda" among
`mixer_layers`: `kda` has the equations; a cut stack names its published
layers under layer_indices), kda_lower_bound (the decay gate's bound, in
(-5.9, 0)), short_conv_kernel_size, head_dim (a KDA head's d_k = d_v; its
heads are num_attention_heads), q_lora_rank null beside the other four
latent keys (DeepSeek-V2-Lite's form: q = x W_q, `wq`, no wq_a and no
q_a_norm), gated_attention_proj_granularity_type head_wise (attention_gate
"per_head", on the latent layer too: `wg`, before `wo`), n_group and
topk_group (DeepSeek-V3's group limit, `noaux_tc`: the experts are n_group
runs of neighbours, a group's score the sum of its two largest s + b, the
top k inside the topk_group best groups; `layers.moe_ffn(n_group=,
topk_group=)`), and the family's names mapped (BAILING):
moe_router_enable_expert_bias (use_expert_bias), score_function
(router_scoring), num_shared_experts with
moe_shared_expert_intermediate_size (n_shared_experts of that width),
rope_interleave (rope_interleaved). What the family can switch on and the
builder does not build is refused by name (BAILING_ONLY: use_kda_lora,
no_kda_lora false, kda_safe_gate false, mtp_use_kda, group_norm_size other
than 1, use_nGPT, up_proj_norm, value_norm, use_mla_nope,
scale_router_input, use_bias, use_qkv_bias, linear_silu false, use_qk_norm
false, num_kv_heads_for_linear_attn other than 0, num_nextn_predict_layers
other than 0: with mtp_loss_scaling_factor 0 the published loss has no term
of the module, and none is built), as is a non-zero entry of
expert_swiglu_limit_list or share_expert_swiglu_limit_list on a kept layer.
seq_aux, max_window_layers, qk_head_dim (held to qk_nope_head_dim +
qk_rope_head_dim), rotary_dim and mtp_loss_scaling_factor are not read. As
a key of its own: `kda_dt_bias_range` ((-1, 0): the decay bias's start). A
KDA layer's parameters: wq, conv_q, wk, conv_k, wv, conv_v, wf, dt_bias,
a_log, wbeta, o_norm, wg, wo; a latent layer's without a query rank and
with its gate: wq, wkv_a, kv_a_norm, wkv_b, wg, wo.
A Mamba layer's parameters: w_in, conv, [conv.bias], w_x, w_dt, dt_bias,
a_log, d, w_out; a memory unit's: w_in, w_out; a differential attention's:
wq, [wq.bias], then where it makes its own keys and values wk, [wk.bias],
wv, [wv.bias], then lambda_q1, lambda_k1, lambda_q2, lambda_k2, subln, wo,
[wo.bias]; a LayerNorm's weight then `<role>.bias`; a fused MLP's w_gate_up,
w_down.

Multi-token prediction (num_nextn_predict_layers 1; DeepSeek-V3,
arXiv:2412.19437, section 2.2; GLM-4.7-Flash, `model_type: glm4_moe_lite`):
one module behind the trunk, outside its count of layers. With s_i the
state after the last trunk layer and N_f the final norm, the module reads
x_i = W_eh [N_e(Emb(t_(i+1))); N_h(N_f(s_i))] (the embedding first and the
normed state, as the family's public inference code has them; W_eh [2 D,
D], no bias), runs one whole decoder layer of its own on x at the same
positions (the trunk's layer code at index num_hidden_layers: latent or
plain attention, then the experts where the model has any, that index
being past the leading dense layers), and gives logits'_i = W_out N_s(y_i)
against t_(i+2). `Emb` and `W_out` are the trunk's own parameters, each ONE
variable with two uses: the embedding's gradient is two scatter-adds summed
and the head's two matmuls' summed, where core/backward.py accumulates a
variable's several uses. The loss is L_main + mtp_loss_weight x L_mtp. The
module is refused over a looped stack, over hc_mult > 1, with a tied head
and with a pattern by layer (layer_types, rope_layout,
sliding_window_layout, full_attention_interval): its layer would have no
entry there. Every op of the module carries its role (`mtp.0`) under
lowering.ROLE_ATTR, which `op_scope` puts before the op's instance. At the
weight of 1 a norm starts from, N_h of a normed state is the identity: a
check that is to see `hnorm` moves its weight off 1 first.

A configuration that is one chip's share of a layer divided over several
says so under `share`: {"chips": n, "chip": i, "published": {key: the whole
model's count}}. The counts the config gives for heads, experts and
vocabulary are then what this chip holds; the router keeps the published
number of experts as its columns and top-k is over all of them, the chip
computes experts chip * held .. chip * held + held - 1, and attention's and
the experts' outputs are the partial sums of what is held.

Parameters are created in the order the reference reads them: embedding;
a layer's [attention hyper-connection: Phi, b, alpha], input norm, then Wq,
Wk, Wv, q norm, k norm, [W_g, the gate a head], Wo (attention), or W_qa, the
q norm, W_qb, W_kva,
the kv norm, W_kvb, Wo (latent attention), or
W_qkvz, W_ba, the convolution's filter, dt_bias, A_log, the gated norm's
weight, W_out (gated delta net) or w_in, the convolution's filter, w_out
(short_conv), [the mixer's outgoing norm], [the FFN's hyper-connection: Phi,
b, alpha], post-attention norm, then router, [the expert bias], gate, up,
down (experts; then the shared expert's gate, up, down and [its sigmoid
gate's weight]) or gate, up, down (dense), [the FFN's outgoing norm]; final
norm; [the module's: enorm, hnorm, eh_proj, then its layer's as any
layer's, then shared_head.norm]; [the exit gate's weight and bias]; head
(none of its own where it is tied). The bracketed ones exist with hc_mult > 1, sandwich_norm,
use_expert_bias, shared_expert_gate, exit_gate and attention_gate
"per_head". A parameter is
named by layer and role, `layer_<i>.<role>` (`layer_0.wq`, `layer_0.wg`,
`layer_3.experts.w_gate`, `layer_3.experts.expert_bias`,
`layer_1.attn_hc.phi`, `layer_1.ffn_hc.alpha`, `layer_1.wkv_b`) and
`embedding`,
`final_norm`, the module's `layer_<num_hidden_layers>.<role>` (as a
checkpoint names them: `.enorm`, `.hnorm`, `.eh_proj`,
`.shared_head.norm` beside the layer's own), `exit_gate.w`,
`exit_gate.b`, `head`: the passes of a looped model find their weights by
name.
"""
import contextlib

import paddle_tpu as fluid
from ..core.lowering import PASS_ATTR, ROLE_ATTR, SCOPE_ATTR

DEFAULTS = {
    "num_experts": 0, "num_experts_per_tok": 0, "norm_topk_prob": False,
    "rms_norm_eps": 1e-5, "rope_theta": 10000.0, "hidden_act": "silu",
    "attention_bias": False, "clip_qkv": None, "tie_word_embeddings": False,
    "initializer_range": 0.02, "router_aux_loss_coef": 0.01,
    "router_z_loss_coef": 0.001, "qk_norm": False, "rope_scaling": None,
    "router_input": "own", "window": None,
    "moe_primary_router_apply_softmax": True, "norm_zero_centered": False,
    "attention_gate": False, "partial_rotary_factor": 1.0,
    "full_attention_interval": 1, "shared_expert_intermediate_size": 0,
    "mlp_only_layers": [], "decoder_sparse_step": 1, "total_ut_steps": 1,
    "early_exit_threshold": 1, "sandwich_norm": False, "exit_gate": False,
    "exit_entropy_coef": 0.0, "conv_bias": False, "num_dense_layers": 0,
    "use_expert_bias": False, "routed_scaling_factor": 1,
    "router_scoring": "softmax", "expert_bias_initializer_range": 0.0,
    "shared_expert_gate": True, "router_renorm_epsilon": None,
    "hc_mult": 1, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
    "mhc_h_res_clamp_min": -30.0, "mhc_h_res_clamp_max": 30.0,
    "hc_alpha_init": 0.5, "hc_res_diag_init": 1.0,
    "num_nextn_predict_layers": 0, "n_group": 1, "topk_group": 1,
    "moe_layer_freq": 1, "rope_interleaved": False, "mtp_loss_weight": 0.3,
    "mb_per_layer": 0, "differential_attention": False,
    "mlp_gate_up_fused": False, "mamba_d_state": 16, "mamba_d_conv": 4,
    "mamba_expand": 2, "mamba_dt_rank": "auto", "lambda_initializer_range": 0.1,
    "embd_pdrop": 0, "resid_pdrop": 0, "mlp_bias": False,
    "lm_head_bias": False, "embedding_multiplier": 1,
    "residual_multiplier": 1, "attention_multiplier": None,
    "logits_scaling": 1, "mamba_conv_bias": True, "mamba_proj_bias": False,
    "mamba_n_groups": 1, "position_embedding_type": "rope",
    "moe_latent_size": 0, "moe_apply_router_weight_on_input": False,
    "moe_router_logit_softcapping": 0}
# the stream every expert bias is drawn from, whatever the program's seed:
# the draw the LFM2 cell's limits were read under (PERF.md section 4)
EXPERT_BIAS_SEED = 39
# SmallThinker's and LFM2's key -> the key the builder reads
ALIASES = {"moe_ffn_hidden_size": "intermediate_size",
           "moe_num_primary_experts": "num_experts",
           "moe_num_active_primary_experts": "num_experts_per_tok",
           "norm_eps": "rms_norm_eps",
           # DeepSeek-V3's names (Xing4.0's config.json)
           "n_routed_experts": "num_experts",
           "first_k_dense_replace": "num_dense_layers",
           "scoring_func": "router_scoring",
           # laguna's name (Laguna-S-2.1's config.json)
           "moe_routed_scaling_factor": "routed_scaling_factor"}
# the keys latent attention needs, all or none; q_lora_rank alone may be
# null beside the others (DeepSeek-V2-Lite's form: q = x W_q, no low rank)
LATENT_KEYS = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
               "qk_rope_head_dim", "v_head_dim")
# bailing_hybrid's names (Ling-3.0-flash's config.json; known where the
# config has layer_group_size) -> the key the builder reads
BAILING = {"moe_router_enable_expert_bias": "use_expert_bias",
           "score_function": "router_scoring",
           "num_shared_experts": "n_shared_experts",
           "rope_interleave": "rope_interleaved"}
# what a bailing_hybrid config may say in one way only: the other is refused
# by name (`_bailing_hybrid`)
BAILING_ONLY = (("use_kda_lora", False), ("no_kda_lora", True),
                ("kda_safe_gate", True), ("mtp_use_kda", False),
                ("group_norm_size", 1), ("use_nGPT", False),
                ("up_proj_norm", False), ("value_norm", False),
                ("use_mla_nope", False), ("scale_router_input", False),
                ("use_bias", False), ("use_qkv_bias", False),
                ("linear_silu", True), ("use_qk_norm", True),
                ("num_kv_heads_for_linear_attn", 0),
                ("num_nextn_predict_layers", 0))
# the stream a KDA mixer's decay bias is drawn from, whatever the program's
# seed (the draw the Ling-3.0-flash cell's limits were read under)
KDA_DT_SEED = 71
# the stream a Mamba mixer's Delta bias is drawn from, whatever the program's
# seed (the draw the Phi-4-mini-flash cell's limits were read under)
MAMBA_DT_SEED = 54
# a layer's kind in `layer_types` -> its mixer here; granitemoehybrid's
# `mamba` is a Mamba-2 mixer ("mamba2": the config's mamba_n_heads and
# mamba_d_head tell it from the Mamba-1 mixer that mb_per_layer builds) and
# is known only to a config that has those keys
LAYER_TYPES = {"conv": "short_conv", "full_attention": "attention",
               "attention": "attention"}
# laguna's: an attention layer behind the window `sliding_window`, known
# where the config has rope_parameters or num_attention_heads_per_layer (a
# geometry by layer)
SLIDING = "sliding_attention"
# the stream a Mamba-2 mixer's Delta bias is drawn from (MAMBA_DT_SEED's
# kind: the draw the granite-4.0-h-micro cell's limits were read under)
MAMBA2_DT_SEED = 57
# nemotron_h's names (known where the config has hybrid_override_pattern) ->
# the key the builder reads
NEMOTRON_H = {"mamba_num_heads": "mamba_n_heads",
              "mamba_head_dim": "mamba_d_head",
              "ssm_state_size": "mamba_d_state",
              "conv_kernel": "mamba_d_conv", "n_groups": "mamba_n_groups",
              "expand": "mamba_expand", "use_conv_bias": "mamba_conv_bias",
              "layer_norm_epsilon": "rms_norm_eps",
              "mlp_hidden_act": "hidden_act",
              "moe_shared_expert_intermediate_size":
              "shared_expert_intermediate_size"}
# what the family's modeling file always does and its config.json therefore
# does not carry; a config that has the key says otherwise
NEMOTRON_H_ALWAYS = {"router_scoring": "sigmoid", "use_expert_bias": True,
                     "router_renorm_epsilon": 1e-20,
                     "router_aux_loss_coef": 0, "router_z_loss_coef": 0,
                     "shared_expert_gate": False}
# a letter of hybrid_override_pattern -> the layer's ONE branch, (mixer,
# FFN): a Mamba-2 mixer, attention, routed experts or a dense MLP
PATTERN = {"M": ("mamba2", "none"), "*": ("attention", "none"),
           "E": ("none", "experts"), "-": ("none", "dense")}
# the keys a gated delta net needs
LINEAR_KEYS = ("linear_num_key_heads", "linear_num_value_heads",
               "linear_key_head_dim", "linear_value_head_dim",
               "linear_conv_kernel_dim")


def resolve(cfg):
    """`cfg` over DEFAULTS, refusing what the builder cannot build rather
    than building something else under the model's name. Adds what the
    builder derives: head_dim, rotary_dim, experts_held and first_expert
    (the share), dense_intermediate_size (the dense FFN's width, where
    `intermediate_size` became an expert's), `latent` (the attention form),
    rope_inv_freq, rope_table_scale and attention_scale (YaRN's, or None, 1
    and None), `rope_tables` (the rotary parameter sets made, (kind,
    rotary_dim) each) and the per-layer patterns
    `rope_layers`, `window_layers`, `geometry_layers` (a layer's own heads
    and rotary parameters, {} where the program's single values hold:
    `_geometry_by_layer`), `mixer_layers` ("attention",
    "gated_delta", "short_conv", "mamba", "gmu" or "mamba2") and
    `ffn_layers` ("dense" or
    "experts"), each with one entry more than the trunk has layers where
    there is a multi-token-prediction module (`mtp_layers` 1): the module's
    layer, an attention layer with the model's experts."""
    c = dict(DEFAULTS, **cfg)
    for theirs, ours in tuple(ALIASES.items()) + (
            tuple(BAILING.items()) if "layer_group_size" in c else ()):
        if theirs in c:
            c[ours] = c[theirs]
    c["dense_intermediate_size"] = c.get("intermediate_size")
    if c["num_experts"] and "moe_intermediate_size" in c:
        c["intermediate_size"] = c["moe_intermediate_size"]
    c.setdefault("num_key_value_heads", c["num_attention_heads"])
    # DeepSeek-V3's router and shared experts, in the keys the builder reads
    if "topk_method" in c:
        if c["topk_method"] not in ("noaux_tc", "greedy"):
            raise NotImplementedError(
                "causal_lm builds topk_method noaux_tc (the top k chosen "
                "over the scores + a correction bias) or greedy, the config "
                "has %r" % (c["topk_method"],))
        c["use_expert_bias"] = c["topk_method"] == "noaux_tc"
    if c.get("n_shared_experts"):
        # so many SwiGLUs of an expert's width, every token through all of
        # them, added as they are: one SwiGLU of their summed width, ungated
        c["shared_expert_intermediate_size"] = c["n_shared_experts"] * c.get(
            "moe_shared_expert_intermediate_size", c["intermediate_size"])
        c["shared_expert_gate"] = False
    pattern = c.get("hybrid_override_pattern")
    if pattern is not None:
        # nemotron_h: the family's names, what its modeling file always
        # does, and no positional term in any layer (rope_theta stays in the
        # file, unread)
        for theirs, ours in NEMOTRON_H.items():
            if theirs in c:
                c[ours] = c[theirs]
        for key, always in NEMOTRON_H_ALWAYS.items():
            if key not in cfg:
                c[key] = always
        c["use_expert_bias"] = bool(c["use_expert_bias"]
                                    and c["num_experts"])
        c["rope_theta"] = None
    c["mb_per_layer"] = int(c["mb_per_layer"])
    # granitemoehybrid's names: no routed experts is a dense model, whose
    # MLP's width is shared_intermediate_size, its first matrix gate and
    # value in one; no positional term under `nope`
    if "num_local_experts" in c:
        if c["num_local_experts"] or c.get("num_experts_per_tok"):
            raise NotImplementedError(
                "causal_lm builds num_local_experts 0 (a dense MLP of "
                "shared_intermediate_size), the config has %r routed, %r a "
                "token" % (c["num_local_experts"],
                           c.get("num_experts_per_tok")))
        c["num_experts"] = c["num_experts_per_tok"] = 0
    if "shared_intermediate_size" in c:
        c["dense_intermediate_size"] = c["shared_intermediate_size"]
        c["mlp_gate_up_fused"] = True
    if c["position_embedding_type"] not in ("rope", "nope"):
        raise NotImplementedError(
            "causal_lm builds position_embedding_type rope or nope (no "
            "positional term in any layer), the config has %r"
            % (c["position_embedding_type"],))
    if c["position_embedding_type"] == "nope":
        c["rope_theta"] = None
    for key in ("embedding_multiplier", "residual_multiplier",
                "logits_scaling"):
        if not c[key] > 0:
            raise ValueError("%s %r: a positive number is needed"
                             % (key, c[key]))
    # a bias in the attention's projections is built for differential
    # attention, one on the convolution for a Mamba mixer's: the other
    # mixers' references have neither
    for key, under in (("attention_bias", "differential_attention"),
                       ("conv_bias", "mb_per_layer")):
        if c[key] not in (False, True) or c[key] and not c[under]:
            raise NotImplementedError(
                "causal_lm builds %s=False, and True under %s only; the "
                "config has %r" % (key, under, c[key]))
    for key, want in (("clip_qkv", None), ("embd_pdrop", 0),
                      ("resid_pdrop", 0), ("mlp_bias", False),
                      ("lm_head_bias", False),
                      ("moe_primary_router_apply_softmax", True),
                      ("decoder_sparse_step", 1),
                      ("moe_apply_router_weight_on_input", False),
                      ("moe_router_logit_softcapping", 0),
                      ("early_exit_threshold", 1), ("moe_layer_freq", 1)):
        if c[key] != want:
            raise NotImplementedError(
                "causal_lm builds %s=%r only, the config has %r"
                % (key, want, c[key]))
    # DeepSeek-V3's group limit on the router's choice: n_group runs of
    # neighbouring experts, the top k inside a token's topk_group best
    c["n_group"], c["topk_group"] = int(c["n_group"]), int(c["topk_group"])
    if not 1 <= c["topk_group"] <= c["n_group"]:
        raise NotImplementedError(
            "causal_lm builds topk_group in 1 .. n_group, the config has "
            "topk_group %d of n_group %d" % (c["topk_group"], c["n_group"]))
    c["group_limited"] = bool(c["num_experts"]) \
        and c["topk_group"] < c["n_group"]
    if c["group_limited"] and not c["use_expert_bias"]:
        raise NotImplementedError(
            "causal_lm builds a group limit on the router's choice (n_group "
            "%d, topk_group %d) under topk_method noaux_tc (a sigmoid "
            "router's scores + a correction bias), where a group's score is "
            "the sum of its two largest; the config has no such router"
            % (c["n_group"], c["topk_group"]))
    # the layers named are dense where the model has experts: built as a
    # leading run, which is num_dense_layers
    only = [int(i) for i in c["mlp_only_layers"]]
    if only:
        if only != list(range(len(only))):
            raise NotImplementedError(
                "causal_lm builds mlp_only_layers as a leading run (0 .. n - "
                "1: num_dense_layers n), the config has %r"
                % (c["mlp_only_layers"],))
        c["num_dense_layers"] = len(only)
    # laguna's gate: one scalar a head from a projection of its own
    if "gating" in c:
        if c["gating"] != "per-head":
            raise NotImplementedError(
                "causal_lm builds gating 'per-head' (sigmoid(x W_g) a head "
                "on the core's output), the config has %r" % (c["gating"],))
        c["attention_gate"] = "per_head"
    if "gated_attention_proj_granularity_type" in c:
        # bailing_hybrid's name for the same gate
        if c["gated_attention_proj_granularity_type"] != "head_wise":
            raise NotImplementedError(
                "causal_lm builds gated_attention_proj_granularity_type "
                "head_wise (sigmoid(x W_g) a head on the core's output), the "
                "config has %r"
                % (c["gated_attention_proj_granularity_type"],))
        c["attention_gate"] = "per_head"
    if c["attention_gate"] not in (False, True, "per_head"):
        raise NotImplementedError(
            "causal_lm builds attention_gate false, true (a gate a channel "
            "from a twice-wide W_q) or 'per_head', the config has %r"
            % (c["attention_gate"],))
    # LayerNorm with weight and bias where the config names its epsilon so
    c["norm_type"] = "layer_norm" if "layer_norm_eps" in c else "rms_norm"
    if "layer_norm_eps" in c:
        c["rms_norm_eps"] = c["layer_norm_eps"]
    if c["mb_per_layer"] and "rope_theta" not in cfg:
        c["rope_theta"] = None      # the family has no positional term
    scaling = c["rope_scaling"]
    if scaling is not None and scaling.get(
            "type", scaling.get("rope_type")) != "yarn":
        raise NotImplementedError(
            "causal_lm builds rope_scaling null or of type yarn, the config "
            "has %r" % (scaling,))
    if scaling is not None:
        missing = [key for key in ("factor",
                                   "original_max_position_embeddings")
                   if key not in scaling]
        if missing:
            raise ValueError("rope_scaling of type yarn needs %s" % missing)
    c["hc_mult"] = int(c["hc_mult"])
    if c["hc_mult"] < 1:
        raise ValueError("hc_mult %d: a model has at least one residual "
                         "stream" % c["hc_mult"])
    c["latent"] = any(c.get(key) is not None for key in LATENT_KEYS)
    if c["latent"]:
        missing = [key for key in LATENT_KEYS[1:] if c.get(key) is None]
        if missing:
            raise NotImplementedError(
                "causal_lm builds latent attention with a low-rank kv and a "
                "head of two parts (q_lora_rank alone may be null: q = x "
                "W_q): the config lacks %s" % missing)
        c.setdefault("q_lora_rank", None)
        if c["num_key_value_heads"] != c["num_attention_heads"]:
            raise NotImplementedError(
                "causal_lm builds latent attention with a key/value head a "
                "query head, the config has %d on %d"
                % (c["num_attention_heads"], c["num_key_value_heads"]))
        if c["qk_norm"] or c["attention_gate"] not in (False, "per_head"):
            raise NotImplementedError(
                "causal_lm builds latent attention without qk_norm and "
                "without a gate, or with attention_gate 'per_head'; the "
                "config has qk_norm %r, attention_gate %r"
                % (c["qk_norm"], c["attention_gate"]))
        c.setdefault("head_dim", c["qk_nope_head_dim"]
                     + c["qk_rope_head_dim"])
    if pattern is not None:
        # the family's MLPs, shared experts and routed experts are all
        # ungated: act(x W_up) W_down
        if c["hidden_act"] != "relu2":
            raise NotImplementedError(
                "causal_lm builds mlp_hidden_act relu2 (ungated MLPs and "
                "experts of two matrices) under hybrid_override_pattern, "
                "the config has %r" % (c["hidden_act"],))
    elif c["hidden_act"] not in (("silu", "relu") if c["num_experts"]
                                 else ("silu",)):
        raise NotImplementedError(
            "causal_lm builds hidden_act silu, and relu in routed experts "
            "(relu2 under hybrid_override_pattern); the config has %r"
            % (c["hidden_act"],))
    c["ffn_gated"] = pattern is None
    if c["moe_latent_size"] and pattern is None:
        raise NotImplementedError(
            "causal_lm builds moe_latent_size (experts in a latent space) "
            "under hybrid_override_pattern only")
    if c["router_input"] not in ("own", "pre_attention"):
        raise NotImplementedError("causal_lm builds router_input own or "
                                  "pre_attention, the config has %r"
                                  % (c["router_input"],))
    if c["tie_word_embeddings"] not in (False, True):
        raise NotImplementedError("causal_lm builds tie_word_embeddings "
                                  "false or true, the config has %r"
                                  % (c["tie_word_embeddings"],))
    if c["router_scoring"] not in ("softmax", "sigmoid"):
        raise NotImplementedError("causal_lm builds router_scoring softmax "
                                  "or sigmoid, the config has %r"
                                  % (c["router_scoring"],))
    if c["router_scoring"] == "sigmoid" and c["num_experts"]:
        for key in ("router_aux_loss_coef", "router_z_loss_coef"):
            if c[key]:
                raise NotImplementedError(
                    "causal_lm builds %s=0 under router_scoring sigmoid (the "
                    "term is defined on a softmax router's probabilities), "
                    "the config has %r" % (key, c[key]))
    if c["use_expert_bias"] and c["router_scoring"] != "sigmoid":
        raise NotImplementedError(
            "causal_lm builds use_expert_bias under router_scoring sigmoid "
            "only, the config has router_scoring %r"
            % (c["router_scoring"],))
    if c["qk_norm"] not in (False, True, "head"):
        raise NotImplementedError("causal_lm builds qk_norm false, true (all "
                                  "channels) or 'head', the config has %r"
                                  % (c["qk_norm"],))
    c["total_ut_steps"] = int(c["total_ut_steps"])
    if c["total_ut_steps"] < 1:
        raise ValueError("total_ut_steps %d: a model runs its layers at "
                         "least once" % c["total_ut_steps"])
    if c["total_ut_steps"] > 1 and c["hc_mult"] > 1:
        raise NotImplementedError(
            "causal_lm runs a stack total_ut_steps=%d times over one "
            "residual stream, not over hc_mult=%d"
            % (c["total_ut_steps"], c["hc_mult"]))
    if c["total_ut_steps"] > 1 and c["num_experts"]:
        raise NotImplementedError(
            "causal_lm runs a stack of dense layers total_ut_steps=%d times, "
            "not one with routed experts (their auxiliary losses and "
            "assignment counts have no meaning summed over passes yet)"
            % c["total_ut_steps"])
    if c["exit_gate"] and c["total_ut_steps"] == 1:
        raise ValueError("exit_gate weighs the passes of a looped model; "
                         "total_ut_steps is 1")
    c["mtp_layers"] = mtp = int(c["num_nextn_predict_layers"])
    if mtp not in (0, 1):
        raise NotImplementedError(
            "causal_lm builds num_nextn_predict_layers 0 or 1 (one "
            "multi-token-prediction module: a second would read the first's "
            "state and the tokens two ahead), the config has %r"
            % (c["num_nextn_predict_layers"],))
    if mtp and pattern is not None \
            and c.get("mtp_hybrid_override_pattern", "*E") != "*E":
        raise NotImplementedError(
            "causal_lm builds a multi-token-prediction module of one layer "
            "with two branches, attention then experts "
            "(mtp_hybrid_override_pattern '*E'), the config has %r"
            % (c["mtp_hybrid_override_pattern"],))
    if mtp:
        for key, want in (("total_ut_steps", 1), ("hc_mult", 1),
                          ("tie_word_embeddings", False),
                          ("full_attention_interval", 1),
                          ("layer_types", None), ("rope_layout", None),
                          ("sliding_window_layout", None)):
            if c.get(key) != want:
                raise NotImplementedError(
                    "causal_lm builds a multi-token-prediction module "
                    "(num_nextn_predict_layers %d) behind a trunk with %s=%r "
                    "only, the config has %r" % (mtp, key, want, c.get(key)))
    if "head_dim" not in c:
        if c["hidden_size"] % c["num_attention_heads"]:
            raise ValueError("hidden_size %d is not a multiple of %d heads"
                             % (c["hidden_size"], c["num_attention_heads"]))
        c["head_dim"] = c["hidden_size"] // c["num_attention_heads"]
    if c["num_attention_heads"] % c["num_key_value_heads"]:
        raise ValueError("%d query heads are no multiple of %d key/value "
                         "heads" % (c["num_attention_heads"],
                                    c["num_key_value_heads"]))
    # the share: the counts above are what is held here; the router's width
    # is the published number of experts
    share = c.get("share") or {}
    c["experts_held"] = c["num_experts"]
    published = share.get("published", {})
    c["num_experts"] = next(
        (published[key] for key in ("moe_num_primary_experts", "num_experts",
                                    "n_routed_experts") if key in published),
        c["num_experts"])
    c["first_expert"] = share.get("chip", 0) * c["experts_held"] \
        if c["experts_held"] != c["num_experts"] else 0
    if c["first_expert"] + c["experts_held"] > c["num_experts"]:
        raise ValueError("chip %d cannot hold %d of %d experts"
                         % (share.get("chip", 0), c["experts_held"],
                            c["num_experts"]))
    if c["group_limited"]:
        group = c["num_experts"] // c["n_group"]
        if c["num_experts"] % c["n_group"] or group < 2 \
                or c["topk_group"] * group < c["num_experts_per_tok"]:
            raise NotImplementedError(
                "causal_lm builds n_group %d, topk_group %d over %d experts "
                "as groups of two or more neighbours that divide the "
                "experts, the kept ones holding num_experts_per_tok %d"
                % (c["n_group"], c["topk_group"], c["num_experts"],
                   c["num_experts_per_tok"]))
    layers = c["num_hidden_layers"]
    for key in ("rope_layout", "sliding_window_layout"):
        if key in c and len(c[key]) < layers:
            raise ValueError("%s has %d entries for %d layers"
                             % (key, len(c[key]), layers))
    # the module's layer (index `layers`) is one entry more in every pattern
    c["rope_layers"] = [c["rope_theta"] is not None
                        and bool(c.get("rope_layout", [1] * layers)[i])
                        for i in range(layers)] \
        + [c["rope_theta"] is not None] * mtp
    c["window_layers"] = [
        c["sliding_window_size"]
        if c.get("sliding_window_layout", [0] * layers)[i] else None
        for i in range(layers)] + [None] * mtp
    c["rotary_dim"] = c["qk_rope_head_dim"] if c["latent"] \
        else int(c["head_dim"] * c["partial_rotary_factor"])
    c["rope_inv_freq"], c["rope_table_scale"], c["attention_scale"] = \
        yarn_table(scaling, c["rope_theta"], c["rotary_dim"], c["head_dim"]) \
        if scaling is not None else (None, 1.0, None)
    # the rotary parameter sets a program has, (kind, rotary_dim) each: one
    # table of YaRN's here, two under a geometry by layer
    c["rope_tables"] = [("yarn", c["rotary_dim"])] if scaling is not None \
        else []
    # a layer's own geometry: one entry a layer of what `_layer` puts in
    # place of the program's single value, nothing where there is none
    c["geometry_layers"] = [{}] * (layers + mtp)
    c["geometry_by_layer"] = "rope_parameters" in c \
        or "num_attention_heads_per_layer" in c
    if c["attention_multiplier"] is not None:
        # the scores' scale itself, what the cores are given: not a second
        # multiply behind head_dim^-0.5
        if scaling is not None or c["latent"]:
            raise NotImplementedError(
                "causal_lm builds attention_multiplier on plain attention "
                "without rope_scaling")
        c["attention_scale"] = float(c["attention_multiplier"])
    if c["rotary_dim"] % 2 or not 0 < c["rotary_dim"] <= c["head_dim"]:
        raise ValueError("partial_rotary_factor %r of a head of %d turns %d "
                         "channels: not an even number in (0, %d]"
                         % (c["partial_rotary_factor"], c["head_dim"],
                            c["rotary_dim"], c["head_dim"]))
    interval = int(c["full_attention_interval"])
    if "layer_types" in c:
        kinds = list(c["layer_types"])
        known = dict(LAYER_TYPES, mamba="mamba2") if "mamba_n_heads" in c \
            else dict(LAYER_TYPES, **{SLIDING: "attention"}) \
            if c["geometry_by_layer"] else LAYER_TYPES
        unknown = sorted(set(kinds) - set(known))
        # a published list over a stack cut short says which layers were
        # kept where it is one kind throughout, or where the share says that
        # it is the published model's whole list: the stack is then its
        # first layers
        if unknown or len(kinds) < layers or (
                len(kinds) > layers and len(set(kinds)) > 1
                and len(kinds) != published.get("num_hidden_layers")):
            raise NotImplementedError(
                "causal_lm builds layer_types of %s, one a layer (more only "
                "of one kind, or the share's published num_hidden_layers of "
                "them: the first are built); the config has %d for %d "
                "layers%s"
                % (sorted(known), len(kinds), layers,
                   ", among them %s" % unknown if unknown else ""))
        c["mixer_layers"] = [known[kind] for kind in kinds[:layers]]
    elif pattern is not None:
        _one_branch_layers(c, pattern, published)
    elif "layer_group_size" in c:
        _bailing_hybrid(c, published)
    else:
        c["mixer_layers"] = ["attention" if (i + 1) % interval == 0
                             else "gated_delta" for i in range(layers)] \
            + ["attention"] * mtp
    if c["geometry_by_layer"]:
        _geometry_by_layer(c, published)
    c["reads_layers"] = ["own"] * (layers + mtp)
    c["lambda_init_layers"] = [None] * (layers + mtp)
    c["memory_layer"] = c["kv_layer"] = None
    if c["mb_per_layer"]:
        _decoder_hybrid_decoder(c, published.get("num_hidden_layers",
                                                 layers))
    elif c["differential_attention"]:
        raise NotImplementedError(
            "causal_lm builds differential_attention under mb_per_layer "
            "(lambda_init is a function of the published layer index)")
    if "mamba2" in c["mixer_layers"]:
        _mamba2(c, published)
    if "short_conv" in c["mixer_layers"]:
        if "conv_L_cache" not in c:
            raise ValueError("layer_types has conv layers, which need "
                             "conv_L_cache, the filter's taps")
        if c["total_ut_steps"] > 1:
            raise NotImplementedError(
                "causal_lm runs a stack of attention layers total_ut_steps="
                "%d times, not one with short_conv mixers"
                % c["total_ut_steps"])
    dense = min(int(c["num_dense_layers"]), layers) if c["num_experts"] \
        else layers
    if pattern is None:
        c["ffn_layers"] = ["dense"] * dense + ["experts"] * (layers - dense) \
            + ["experts" if c["num_experts"] else "dense"] * mtp
    # keys that say again what others said: held to them, not read twice
    for key, said, mean in (
            ("mlp_layer_types", [{"dense": "dense", "experts": "sparse"}.get(
                kind, kind) for kind in c["ffn_layers"][:layers]],
             "mlp_only_layers, num_dense_layers and num_experts"),
            ("gating_types", [str(c["attention_gate"]).lower()] * layers,
             "gating")):
        if key in c and list(c[key])[:layers] != said:
            raise ValueError(
                "%s %r disagrees with what %s give: %r"
                % (key, list(c[key])[:layers], mean, said))
    if "gated_delta" in c["mixer_layers"]:
        missing = [key for key in LINEAR_KEYS if key not in c]
        if missing:
            raise ValueError("full_attention_interval %d makes gated delta "
                             "nets, which need %s" % (interval, missing))
        if c["linear_num_value_heads"] % c["linear_num_key_heads"]:
            raise ValueError("%d linear value heads are no multiple of %d "
                             "key heads" % (c["linear_num_value_heads"],
                                            c["linear_num_key_heads"]))
    # sdar_moe's (Qwen3-MoE's) window switch: off is all that is built
    if c.get("use_sliding_window"):
        raise NotImplementedError(
            "causal_lm builds use_sliding_window false only (a window is a "
            "layer's, by sliding_window_layout or layer_types); the config "
            "has %r" % (c["use_sliding_window"],))
    _objective(c)
    return c


def _objective(c):
    """The training objective: next-token (no `objective` key, as it always
    was: c["block_diffusion"] None) or `objective: block_diffusion` (BD3-LM,
    arXiv:2503.09573; SDAR's, arXiv:2510.06303), whose keys become
    c["block_diffusion"] = {block_length, mask_token_id, noise_eps}. Under
    it `resolve` refuses by name what `causal_lm` cannot build over two
    copies of a sequence: a window, a looped stack, several streams, a
    multi-token-prediction module, a mixer other than attention, latent or
    differential attention, a tied head, a mask_token_id outside the held
    words (a block_length that does not divide the sequence is `causal_lm`'s
    to refuse: it knows the length)."""
    objective = c.get("objective")
    c["block_diffusion"] = None
    if objective is None:
        return
    if objective != "block_diffusion":
        raise NotImplementedError(
            "causal_lm builds the next-token objective (no `objective` key) "
            "or objective block_diffusion, the config has %r" % (objective,))
    layers = c["num_hidden_layers"]
    for what, found in (
            ("a window (window_layers)",
             any(w is not None for w in c["window_layers"])),
            ("a looped stack (total_ut_steps %d)" % c["total_ut_steps"],
             c["total_ut_steps"] > 1),
            ("several residual streams (hc_mult %d)" % c["hc_mult"],
             c["hc_mult"] > 1),
            ("a multi-token-prediction module (num_nextn_predict_layers)",
             c["mtp_layers"] > 0),
            ("a mixer other than attention (%s)" % sorted(
                set(c["mixer_layers"][:layers]) - {"attention"}),
             set(c["mixer_layers"][:layers]) != {"attention"}),
            ("latent attention", c["latent"]),
            ("differential attention", c["differential_attention"]),
            ("a tied head (tie_word_embeddings)", c["tie_word_embeddings"])):
        if found:
            raise NotImplementedError(
                "causal_lm builds objective block_diffusion (a noised and a "
                "clean copy of every sequence under the block-diffusion "
                "mask) without %s" % what)
    length, mask_id = c.get("block_length"), c.get("mask_token_id")
    if not isinstance(length, int) or length < 1:
        raise ValueError("objective block_diffusion needs block_length, a "
                         "whole number of positions; the config has %r"
                         % (length,))
    if not isinstance(mask_id, int) or not 0 <= mask_id < c["vocab_size"]:
        raise ValueError(
            "objective block_diffusion needs mask_token_id among the %d held "
            "words (0 .. %d), the config has %r"
            % (c["vocab_size"], c["vocab_size"] - 1, mask_id))
    c["block_diffusion"] = {"block_length": length, "mask_token_id": mask_id,
                            "noise_eps": float(c.get("noise_eps", 1e-3))}


def _geometry_by_layer(c, published):
    """laguna's attention (Laguna-S-2.1, `model_type: laguna`), whose
    geometry is a LAYER's: `geometry_layers`, for every attention layer what
    `_layer` puts in place of the program's single value. A head count:
    `num_attention_heads_per_layer` (a list longer than the stack is cut to
    its first layers where it is the share's published num_hidden_layers of
    them; every entry a multiple of the key/value heads, so that W_q, W_g
    and W_o are [D, H_i x head_dim]: head_dim must be given). A window:
    `sliding_window` on the layers whose `layer_types` says
    `sliding_attention` (`window_layers`). Rotary parameters:
    `rope_parameters[layer_types[i]]`, {rope_type default or yarn,
    rope_theta, partial_rotary_factor and, for yarn, `yarn_table`'s keys and
    attention_factor, the factor on cos and sin where given}: rotary_dim,
    rope_inv_freq, rope_table_scale, rope_theta and attention_scale, ONE
    table a KIND of layer (`rope_tables` has them), shared by the layers of
    the kind. A layer that is no attention has no entry."""
    layers, hkv = c["num_hidden_layers"], c["num_key_value_heads"]
    for key, want in (("latent", False), ("differential_attention", False),
                      ("mb_per_layer", 0), ("rope_scaling", None),
                      ("mtp_layers", 0), ("total_ut_steps", 1),
                      ("rope_layout", None), ("sliding_window_layout", None),
                      ("attention_multiplier", None)):
        if c.get(key) != want:
            raise NotImplementedError(
                "causal_lm builds a geometry by layer (rope_parameters, "
                "num_attention_heads_per_layer) with %s=%r only, the config "
                "has %r" % (key, want, c.get(key)))
    if "layer_types" not in c or "head_dim" not in c:
        raise ValueError("a geometry by layer needs layer_types (a layer's "
                         "kind names its rope_parameters and its window) "
                         "and head_dim (no head count gives it)")
    kinds = list(c["layer_types"])[:layers]
    attends = [mixer == "attention" for mixer in c["mixer_layers"][:layers]]
    heads = list(c.get("num_attention_heads_per_layer",
                       [c["num_attention_heads"]] * layers))
    if len(heads) < layers or (
            len(heads) > layers
            and len(heads) != published.get("num_hidden_layers")):
        raise ValueError(
            "num_attention_heads_per_layer has %d entries for %d layers "
            "(more only where it is the share's published num_hidden_layers "
            "of them: the first are built)" % (len(heads), layers))
    heads = [int(n) for n in heads[:layers]]
    wrong = sorted({n for n in heads if n < 1 or n % hkv})
    if wrong:
        raise ValueError("num_attention_heads_per_layer has %r, no multiple "
                         "of %d key/value heads" % (wrong, hkv))
    whole = published.get("num_attention_heads_per_layer")
    if whole is not None and any(
            n * published.get("num_key_value_heads", hkv) != m * hkv
            for n, m in zip(heads, whole)):
        raise ValueError(
            "num_attention_heads_per_layer %r on %d key/value heads is not "
            "the share of the published %r on %r that those key/value heads "
            "carry" % (heads, hkv, list(whole)[:layers],
                       published.get("num_key_value_heads", hkv)))
    window = c.get("sliding_window")
    if SLIDING in kinds and (not isinstance(window, int) or window < 1):
        raise ValueError("layer_types has %s layers, which need "
                         "sliding_window, a whole number of keys; the "
                         "config has %r" % (SLIDING, window))
    c["window_layers"] = [window if kind == SLIDING else None
                          for kind in kinds]
    sets, tables = c.get("rope_parameters"), {}
    if sets is not None:
        for kind in sorted({k for k, a in zip(kinds, attends) if a}):
            if not isinstance(sets.get(kind), dict):
                raise NotImplementedError(
                    "causal_lm builds rope_parameters by layer type, a set "
                    "for each of layer_types' kinds; the config has none for "
                    "%r among %s" % (kind, sorted(sets)))
            tables[kind] = _rope_kind(c, kind, sets[kind])
        c["rope_tables"] = [(tables[kind]["rope_type"],
                             tables[kind]["rotary_dim"])
                            for kind in sorted(tables)]
    c["geometry_layers"] = [
        dict(tables.get(kind, {}), num_attention_heads=n) if attention
        else {} for kind, n, attention in zip(kinds, heads, attends)]
    c["rope_layers"] = [
        attention and g.get("rope_theta", c["rope_theta"]) is not None
        for g, attention in zip(c["geometry_layers"], attends)]


def _rope_kind(c, kind, params):
    """One kind of layer's rotary parameters as `attention` reads them:
    rope_type `default` (theta^(-2i/R) over the R = head_dim x
    partial_rotary_factor first channels of a head) or `yarn`
    (`yarn_table` over those R, cos and sin multiplied by attention_factor
    where the set gives one, else by the table's m(1) / m(0); the scores'
    scale stays head_dim^-1/2 unless the set has mscale_all_dim)."""
    rope_type = params.get("rope_type", "default")
    if rope_type not in ("default", "yarn"):
        raise NotImplementedError(
            "causal_lm builds rope_parameters of rope_type default or yarn, "
            "%s has %r" % (kind, rope_type))
    hd = c["head_dim"]
    factor = params.get("partial_rotary_factor", c["partial_rotary_factor"])
    rotary = int(hd * factor)
    if rotary % 2 or not 0 < rotary <= hd:
        raise ValueError("partial_rotary_factor %r of a head of %d turns %d "
                         "channels on %s layers: not an even number in (0, "
                         "%d]" % (factor, hd, rotary, kind, hd))
    found = dict(rope_type=rope_type, rotary_dim=rotary, rope_inv_freq=None,
                 rope_theta=params.get("rope_theta", c["rope_theta"]),
                 rope_table_scale=1.0, attention_scale=None)
    if rope_type == "yarn":
        missing = [key for key in ("factor",
                                   "original_max_position_embeddings")
                   if key not in params]
        if missing:
            raise ValueError("rope_parameters of rope_type yarn need %s (%s)"
                             % (missing, kind))
        inv_freq, table_scale, scale = yarn_table(
            params, found["rope_theta"], rotary, hd)
        given = params.get("attention_factor")
        found.update(
            rope_inv_freq=inv_freq,
            rope_table_scale=table_scale if given is None else float(given),
            attention_scale=scale if params.get("mscale_all_dim") else None)
    return found


def _bailing_hybrid(c, published):
    """The layer pattern of bailing_hybrid (Ling-3.0-flash) written into c:
    by PUBLISHED index i a layer is latent attention where (i + 1) %
    layer_group_size == 0, else a KDA mixer ("kda" among `mixer_layers`:
    `kda` has the equations). A stack cut short says which published layers
    it kept under `layer_indices` (absent: 0 .. num_hidden_layers - 1).
    Refuses by name what the family's config can switch on and `kda` does
    not build (BAILING_ONLY), a non-zero clamp of a kept layer's experts
    (expert_swiglu_limit_list, share_expert_swiglu_limit_list), and what a
    KDA layer cannot stand beside."""
    layers, group = c["num_hidden_layers"], int(c["layer_group_size"])
    for key, want in BAILING_ONLY + (
            ("total_ut_steps", 1), ("hc_mult", 1), ("sandwich_norm", False),
            ("mb_per_layer", 0), ("rope_layout", None),
            ("sliding_window_layout", None), ("full_attention_interval", 1),
            ("norm_type", "rms_norm")):
        if c.get(key, want) != want:
            raise NotImplementedError(
                "causal_lm builds layer_group_size (KDA mixers and latent "
                "attention) with %s=%r only, the config has %r"
                % (key, want, c[key]))
    if group < 1 or not c["latent"]:
        raise NotImplementedError(
            "causal_lm builds layer_group_size %r as KDA mixers with latent "
            "attention on every layer_group_size-th layer: kv_lora_rank, "
            "qk_nope_head_dim, qk_rope_head_dim and v_head_dim are needed"
            % (c["layer_group_size"],))
    indices = _layer_indices(c, published.get("num_hidden_layers", layers))
    for key in ("expert_swiglu_limit_list",
                "share_expert_swiglu_limit_list"):
        limits = list(c.get(key) or [])
        clamped = [i for i in indices if i < len(limits) and limits[i]]
        if clamped:
            raise NotImplementedError(
                "causal_lm builds experts without a clamp on the SwiGLU: %s "
                "is not 0 on the kept layers %s" % (key, clamped))
    for key in ("kda_lower_bound", "short_conv_kernel_size"):
        if key not in c:
            raise ValueError("layer_group_size makes KDA mixers, which need "
                             "%s" % key)
    if not -5.9 < c["kda_lower_bound"] < 0:
        raise NotImplementedError(
            "causal_lm builds kda_lower_bound in (-5.9, 0): the chunked "
            "rule's exponentials over 16 rows are finite under that bound "
            "(ops/kda_kernels.py); the config has %r"
            % (c["kda_lower_bound"],))
    if "qk_head_dim" in c and c["qk_head_dim"] != \
            c["qk_nope_head_dim"] + c["qk_rope_head_dim"]:
        raise ValueError("qk_head_dim %r is not qk_nope_head_dim + "
                         "qk_rope_head_dim" % (c["qk_head_dim"],))
    c["layer_indices"] = indices
    c["mixer_layers"] = ["attention" if (i + 1) % group == 0 else "kda"
                         for i in indices]


def _one_branch_layers(c, pattern, published):
    """`mixer_layers` and `ffn_layers` of hybrid_override_pattern
    (nemotron_h): a letter a layer, and a layer is ONE branch, h + f(N(h)),
    f a mixer (`M` a Mamba-2 mixer, `*` attention) or an FFN (`E` routed
    experts, `-` a dense MLP); the other list says "none" there. A pattern
    longer than the stack is cut to its first layers where the share says it
    is the published model's whole pattern. A multi-token-prediction
    module's one layer has both branches, `*` then `E`."""
    layers, mtp = c["num_hidden_layers"], c["mtp_layers"]
    unknown = sorted(set(pattern) - set(PATTERN))
    if unknown or len(pattern) < layers or (
            len(pattern) > layers
            and len(pattern) != published.get("num_hidden_layers")):
        raise NotImplementedError(
            "causal_lm builds hybrid_override_pattern of %s, a letter a "
            "layer (more only where it is the share's published "
            "num_hidden_layers of them: the first are built); the config "
            "has %d for %d layers%s"
            % (sorted(PATTERN), len(pattern), layers,
               ", among them %s" % unknown if unknown else ""))
    for key, want in (("total_ut_steps", 1), ("hc_mult", 1),
                      ("sandwich_norm", False), ("router_input", "own"),
                      ("latent", False), ("num_dense_layers", 0),
                      ("qk_norm", False), ("attention_gate", False),
                      ("norm_type", "rms_norm"), ("use_bias", False),
                      ("tie_word_embeddings", False),
                      ("residual_multiplier", 1)):
        if c.get(key, want) != want:
            raise NotImplementedError(
                "causal_lm builds hybrid_override_pattern with %s=%r only, "
                "the config has %r" % (key, want, c.get(key)))
    kinds = [PATTERN[letter] for letter in pattern[:layers]]
    if ("none", "experts") in kinds and not c["num_experts"]:
        raise ValueError("hybrid_override_pattern has E layers, which need "
                         "n_routed_experts")
    c["mixer_layers"] = [mixer for mixer, _ in kinds] + ["attention"] * mtp
    c["ffn_layers"] = [ffn for _, ffn in kinds] + ["experts"] * mtp


def _mamba2(c, published):
    """What a stack with Mamba-2 mixers (granitemoehybrid's `mamba` layers,
    nemotron_h's `M`) needs and refuses: mamba_n_heads x mamba_d_head =
    mamba_expand x hidden_size channels (the published heads', where a
    share of them is held), whole groups of heads under the groups of B and
    C, no bias on the projections."""
    for key, want in (("total_ut_steps", 1), ("hc_mult", 1),
                      ("mamba_proj_bias", False), ("sandwich_norm", False)) \
            + (() if c.get("hybrid_override_pattern")
               else (("mtp_layers", 0),)):
        if c.get(key) != want:
            raise NotImplementedError(
                "causal_lm builds Mamba-2 mixers (layer_types mamba) with "
                "%s=%r only, the config has %r" % (key, want, c.get(key)))
    if c["mamba_conv_bias"] not in (False, True):
        raise NotImplementedError("causal_lm builds mamba_conv_bias false or "
                                  "true, the config has %r"
                                  % (c["mamba_conv_bias"],))
    if "mamba_d_head" not in c:
        raise ValueError("layer_types has mamba layers, which need "
                         "mamba_n_heads and mamba_d_head")
    inner = c["mamba_expand"] * c["hidden_size"]
    heads = next((published[key] for key in ("mamba_num_heads",
                                             "mamba_n_heads")
                  if key in published), c["mamba_n_heads"])
    if heads * c["mamba_d_head"] != inner:
        raise ValueError(
            "%d Mamba-2 heads of %d are not mamba_expand x hidden_size = %d "
            "channels" % (heads, c["mamba_d_head"], inner))
    groups = c["mamba_n_groups"] = int(c["mamba_n_groups"])
    if groups < 1 or c["mamba_n_heads"] % groups:
        raise ValueError("%d Mamba-2 heads are not whole groups of %r"
                         % (c["mamba_n_heads"], c["mamba_n_groups"]))


def _layer_indices(c, published):
    """The published index of every layer a stack kept (`layer_indices`;
    absent: 0 .. num_hidden_layers - 1), held to `published` layers: as
    many as the stack has, rising, under the published depth."""
    layers = c["num_hidden_layers"]
    indices = [int(i) for i in c.get("layer_indices", range(layers))]
    if len(indices) != layers or sorted(set(indices)) != indices \
            or indices[-1] >= published:
        raise ValueError("layer_indices %r: %d rising published indices "
                         "under %d are needed" % (indices, layers, published))
    return indices


def _decoder_hybrid_decoder(c, published):
    """The layer pattern of SambaY (arXiv:2507.06607; `model_type:
    phi4flash`) written into c, by PUBLISHED index i of `published` layers,
    half = published // 2: i < half, the self-decoder: a Mamba mixer where i
    % mb_per_layer == 0, else attention under sliding_window; i == half: a
    Mamba mixer that hands on its scan output (`memory_layer`: its place in
    the stack built); i == half + 1: full attention that hands on its keys
    and values (`kv_layer`); after that, the cross-decoder: a gated memory
    unit ("gmu") reading the memory where i % mb_per_layer == 0, else
    attention reading those keys and values (`reads_layers` "shared"). A
    stack cut short says which published layers it kept under
    `layer_indices` (absent: 0 .. num_hidden_layers - 1). lambda_init of a
    differential attention is 0.8 - 0.6 exp(-0.3 i)."""
    import math
    mb = c["mb_per_layer"]
    for key, want in (("total_ut_steps", 1), ("hc_mult", 1),
                      ("mtp_layers", 0), ("num_experts", 0),
                      ("latent", False), ("qk_norm", False),
                      ("attention_gate", False),
                      ("full_attention_interval", 1), ("layer_types", None),
                      ("rope_layout", None), ("sliding_window_layout", None)):
        if c.get(key) != want:
            raise NotImplementedError(
                "causal_lm builds mb_per_layer %d (Mamba mixers, a memory "
                "and keys and values that later layers read) with %s=%r "
                "only, the config has %r" % (mb, key, want, c.get(key)))
    indices = _layer_indices(c, published)
    half = published // 2
    if half % mb or (half + 1) % mb == 0:
        raise NotImplementedError(
            "causal_lm builds a decoder-hybrid-decoder whose layer %d is a "
            "Mamba mixer and whose layer %d is attention; mb_per_layer %d "
            "makes it otherwise" % (half, half + 1, mb))
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    if c["differential_attention"] and (heads % 2 or kv % 2
                                        or (heads // 2) % (kv // 2)):
        raise ValueError("differential attention pairs the heads: %d on %d "
                         "are not pairs on pairs" % (heads, kv))
    if c["mamba_dt_rank"] == "auto":
        c["mamba_dt_rank"] = -(-c["hidden_size"] // 16)
    c["layer_indices"] = indices
    for k, i in enumerate(indices):
        mamba = i % mb == 0
        c["mixer_layers"][k] = ("mamba" if i <= half else "gmu") if mamba \
            else "attention"
        c["window_layers"][k] = c.get("sliding_window") \
            if not mamba and i < half else None
        c["reads_layers"][k] = "shared" if i > half + 1 else "own"
        if not mamba and c["differential_attention"]:
            c["lambda_init_layers"][k] = 0.8 - 0.6 * math.exp(-0.3 * i)
        if i == half:
            c["memory_layer"] = k
        if i == half + 1:
            c["kv_layer"] = k
    for k, i in enumerate(indices):
        if c["reads_layers"][k] == "shared" and c[
                "memory_layer" if c["mixer_layers"][k] == "gmu"
                else "kv_layer"] is None:
            raise ValueError(
                "layer_indices %r keeps layer %d, which reads what layer %d "
                "hands on, and not that layer" % (
                    indices, i, half if c["mixer_layers"][k] == "gmu"
                    else half + 1))


def yarn_table(scaling, theta, rotary_dim, head_dim):
    """(inv_freq, the factor on cos and sin, the attention's scale) of
    rope_scaling {type: yarn} (arXiv:2309.00071 as DeepSeek-V3's modelling
    code applies it), float32 on the host, once a program. With f_i =
    theta^(-2i/R) over the R/2 pairs: those that turn more than beta_fast
    times over the original length keep f_i, those that turn less than
    beta_slow times get f_i / factor, a linear ramp between (pair lo to pair
    hi): inv_freq_i = f_i (1 - r_i) + (f_i / factor) r_i, r_i = clamp((i -
    lo) / (hi - lo), 0, 1). m(s) = 0.1 s ln(factor) + 1: cos and sin are
    multiplied by m(mscale) / m(mscale_all_dim) and the scores' scale is
    head_dim^(-1/2) m(mscale_all_dim)^2."""
    import math
    import numpy as np
    factor = float(scaling["factor"])
    original = scaling["original_max_position_embeddings"]
    fast, slow = scaling.get("beta_fast", 32), scaling.get("beta_slow", 1)

    def pair(turns):        # the pair that turns so often over `original`
        return rotary_dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    def m(s):
        return 0.1 * s * math.log(factor) + 1.0 if factor > 1 else 1.0

    lo = max(math.floor(pair(fast)), 0)
    hi = min(math.ceil(pair(slow)), rotary_dim - 1)
    i = np.arange(rotary_dim // 2, dtype=np.float32)
    f = np.float32(theta) ** (-2 * i / np.float32(rotary_dim))
    ramp = np.clip((i - lo) / np.float32(max(hi - lo, 1e-3)), 0, 1)
    inv_freq = f * (1 - ramp) + f / np.float32(factor) * ramp
    all_dim = m(scaling.get("mscale_all_dim", 0))
    return [float(x) for x in inv_freq.astype(np.float32)], \
        m(scaling.get("mscale", 1)) / all_dim, head_dim ** -0.5 * all_dim ** 2


def _layer(c, i):
    """The config as layer i sees it: `layer` its index, which names its
    parameters; `rope_theta` None where the pattern gives the layer no
    rotary, `window` its sliding window or None, `ffn` its FFN's kind; and,
    under a geometry by layer, its own num_attention_heads, rotary_dim,
    rope_inv_freq, rope_table_scale, rope_theta, attention_scale and
    rope_type in place of the program's (`_geometry_by_layer`)."""
    cl = dict(c, layer=i, window=c["window_layers"][i],
              ffn=c["ffn_layers"][i], reads=c["reads_layers"][i],
              lambda_init=c["lambda_init_layers"][i],
              rope_theta=c["rope_theta"] if c["rope_layers"][i] else None)
    cl.update(c["geometry_layers"][i])
    return cl


def _attr(c, role, initializer=None):
    """The ParamAttr of one parameter, named by layer and role:
    `layer_<i>.<role>`, or `<role>` outside the layers (no role: a name of
    its own). A pass of a looped model that asks for a name again is given
    the parameter the first pass made (core/layer_helper.py)."""
    if role is not None and "layer" in c:
        role = "layer_%d.%s" % (c["layer"], role)
    return fluid.ParamAttr(name=role, initializer=initializer)


def _matrix(c, role):
    return _attr(c, role, fluid.initializer.Normal(0.0,
                                                   c["initializer_range"]))


def _linear(x, size, c, role, bias=False):
    """x W, and + b where `bias` (the parameter `<role>.bias`, zeros)."""
    return fluid.layers.fc(
        input=x, size=size, num_flatten_dims=2, param_attr=_matrix(c, role),
        bias_attr=_attr(c, role + ".bias", fluid.initializer.Constant(0.0))
        if bias else False)


def _norm(x, c, role=None):
    """The model's norm with its own weight, named by `role` or, called with
    two arguments, by c["role"] where the caller put one there: an RMS norm,
    or (`layer_norm_eps`) a LayerNorm over the last axis with weight and
    bias (`<role>.bias`)."""
    role = role or c.get("role")
    if c["norm_type"] == "layer_norm":
        return fluid.layers.layer_norm(
            x, begin_norm_axis=len(x.shape) - 1, epsilon=c["rms_norm_eps"],
            param_attr=_attr(c, role, fluid.initializer.Constant(1.0)),
            bias_attr=_attr(c, role + ".bias",
                            fluid.initializer.Constant(0.0)))
    return fluid.layers.rms_norm(x, epsilon=c["rms_norm_eps"],
                                 zero_centered=c["norm_zero_centered"],
                                 param_attr=_attr(c, role))


def attention(x, pos, c):
    """Causal self-attention over x [B, T, D], with the window c["window"]
    where the layer has one. QK-norm, where the config has it, is over all
    channels before the head split or ("head") over each head after it;
    rotary positions, where the layer has them, turn the first rotary_dim
    channels of every head of q and k; key/value heads may be fewer than
    query heads; the core is layers.fused_attention. With attention_gate the
    query projection is twice as wide, a head [q, gate], and the context is
    multiplied by sigmoid(gate) before the output projection; with
    attention_gate "per_head" (laguna's) the gate is one scalar a head from
    a projection of its own, sigmoid(x W_g) [B, T, H] (`wg`, no bias), on
    the core's output before W_o. The heads, the rotary's width, table and
    theta and the window are the LAYER's (`_layer`): W_q and W_o are [D, H
    x head_dim] whatever the hidden size is. Under objective block_diffusion
    (c["copies"] = (block_length, T)) x is [B, 2 T, D], the noised copy's
    rows then the clean copy's, pos [B, 2 T] the same T positions twice,
    and the core's mask is block diffusion's in place of the causal one
    (layers.fused_attention has the rule); with c["noised_rows"] = T (the
    last layer) the core's output is cut to the noised copy's rows before
    W_o, and the result is [B, T, D]."""
    d, hd = c["hidden_size"], c["head_dim"]
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    per_head = c["attention_gate"] == "per_head"
    gated = c["attention_gate"] and not per_head
    q, k, v = (_linear(x, n * hd, c, role) for n, role in (
        (2 * h if gated else h, "wq"), (hkv, "wk"), (hkv, "wv")))
    cq, ck = dict(c, role="q_norm"), dict(c, role="k_norm")
    if c["qk_norm"] is True:
        q, k = _norm(q, cq), _norm(k, ck)
    q = fluid.layers.reshape(q, shape=[0, -1, h, 2 * hd if gated else hd])
    k, v = (fluid.layers.reshape(t, shape=[0, -1, hkv, hd]) for t in (k, v))
    if gated:
        q, gate = fluid.layers.split(q, 2, dim=-1)
    if c["qk_norm"] == "head":
        q, k = _norm(q, cq), _norm(k, ck)
    if c["rope_theta"] is not None:
        q, k = (fluid.layers.rotary_embedding(
            t, pos, base=c["rope_theta"], rotary_dim=c["rotary_dim"],
            inv_freq=c["rope_inv_freq"], table_scale=c["rope_table_scale"],
            layout="interleaved" if c["rope_interleaved"] else "half")
            for t in (q, k))
    # under objective block_diffusion the rows are a noised and a clean copy
    # of the sequence and the mask is block diffusion's, the whole of it
    copies = c.get("copies")
    ctx = fluid.layers.fused_attention(
        q, k, v, causal=copies is None, window=c["window"],
        scale=c["attention_scale"], block_diffusion=copies)
    if gated:
        ctx = ctx * fluid.layers.sigmoid(gate)
    if per_head:
        ctx = fluid.layers.elementwise_mul(
            ctx, fluid.layers.sigmoid(_linear(x, h, c, "wg")), axis=0)
    if c.get("noised_rows"):
        # the last layer: nothing reads the clean copy's rows behind the core
        ctx = fluid.layers.crop(ctx, shape=[-1, c["noised_rows"], -1, -1])
    return _linear(fluid.layers.reshape(ctx, shape=[0, -1, h * hd]), d, c,
                   "wo")


def _head_columns(w, heads, widths):
    """A projection's weight [K, heads * sum(widths)], a head's columns the
    parts of `widths` side by side, as one matrix a part, [K, heads *
    width]: the parts come out of matmuls of their own and no pass over
    the activations takes them apart."""
    k = int(w.shape[0])
    parts = fluid.layers.split(
        fluid.layers.reshape(w, shape=[k, heads, sum(widths)]),
        list(widths), dim=-1)
    return [fluid.layers.reshape(part, shape=[k, heads * width])
            for part, width in zip(parts, widths)]


def latent_attention(x, pos, c):
    """Multi-head latent attention (DeepSeek-V2/V3's; arXiv:2405.04434)
    over x [B, T, D]: c_q = N(x W_qa) [q_lora_rank], q = c_q W_qb, a head
    [q_nope (qk_nope_head_dim); q_rope (qk_rope_head_dim)]; [c_kv
    (kv_lora_rank); k_r (qk_rope_head_dim)] = x W_kva, kv = N(c_kv) W_kvb, a
    head [k_nope; v (v_head_dim)]; rotary positions turn q_rope of every
    head and k_r, ONE key that all heads read; a head's score is scale x
    (q_nope . k_nope + q_rope . k_rope), its output P v; then W_o. W_qb and
    W_kvb are one parameter each in the published column order; their
    parts' columns are taken apart as weights (`_head_columns`), so q_nope,
    q_rope, k_nope and v are matmuls' own results and the core
    (layers.fused_attention's latent form) reads them where they lie.
    Rotary pairs are interleaved (2i, 2i + 1) with rope_interleaved, and
    the table is YaRN's where rope_scaling says so. With q_lora_rank null
    (DeepSeek-V2-Lite's form) q = x W_q, `wq` [D, H (dn + dr)], and there is
    no wq_a and no q_a_norm; with attention_gate "per_head" the core's
    output is multiplied by sigmoid(x W_g) [B, T, H] before W_o (`wg`, made
    before `wo`, as `attention` makes it)."""
    layers = fluid.layers
    h, d = c["num_attention_heads"], c["hidden_size"]
    dn, dr, dv = (c[key] for key in ("qk_nope_head_dim", "qk_rope_head_dim",
                                     "v_head_dim"))
    rq, rkv = c["q_lora_rank"], c["kv_lora_rank"]

    def weight(role, shape):
        return layers.create_parameter(shape, "float32",
                                       attr=_matrix(c, role))

    if rq is None:
        # DeepSeek-V2-Lite's form: q = x W_q, one matrix, no low rank
        cq, role, rq = x, "wq", d
    else:
        cq, role = _norm(_linear(x, rq, c, "wq_a"), c, "q_a_norm"), "wq_b"
    w_nope, w_rope = _head_columns(weight(role, [rq, h * (dn + dr)]), h,
                                   (dn, dr))
    q = layers.reshape(layers.matmul(cq, w_nope), shape=[0, -1, h, dn])
    q_rope = layers.reshape(layers.matmul(cq, w_rope), shape=[0, -1, h, dr])
    ckv, k_rope = layers.split(_linear(x, rkv + dr, c, "wkv_a"), [rkv, dr],
                               dim=-1)
    ckv = _norm(ckv, c, "kv_a_norm")
    w_k, w_v = _head_columns(weight("wkv_b", [rkv, h * (dn + dv)]), h,
                             (dn, dv))
    k = layers.reshape(layers.matmul(ckv, w_k), shape=[0, -1, h, dn])
    v = layers.reshape(layers.matmul(ckv, w_v), shape=[0, -1, h, dv])
    k_rope = layers.reshape(k_rope, shape=[0, -1, 1, dr])
    if c["rope_theta"] is not None:
        q_rope, k_rope = (layers.rotary_embedding(
            t, pos, base=c["rope_theta"], inv_freq=c["rope_inv_freq"],
            table_scale=c["rope_table_scale"],
            layout="interleaved" if c["rope_interleaved"] else "half")
            for t in (q_rope, k_rope))
    ctx = layers.fused_attention(
        q, k, v, causal=True, window=c["window"], q_rope=q_rope,
        k_rope=k_rope, scale=c["attention_scale"] or (dn + dr) ** -0.5)
    if c["attention_gate"] == "per_head":
        ctx = layers.elementwise_mul(
            ctx, layers.sigmoid(_linear(x, h, c, "wg")), axis=0)
    return _linear(layers.reshape(ctx, shape=[0, -1, h * dv]), d, c, "wo")


def hyper_connection(x, c, role):
    """layers.mhc_pre on the streams x [B, T, hc_mult * D] for the sub-layer
    `role` ("attn_hc" or "ffn_hc") of layer c["layer"]: (what the sub-layer
    reads, the coefficients, the streams for `mhc_post`). Parameters
    `layer_<i>.<role>.phi`, `.b` and `.alpha`: Phi normal(0,
    initializer_range); alpha hc_alpha_init thrice; b so that at alpha = 0
    H_pre = 1 / n, H_post = 1 and H_res is the Sinkhorn of exp(hc_res_diag_init
    x I) (0: uniform, 1 / n everywhere; large: the identity)."""
    import numpy as np
    n = c["hc_mult"]
    bias = np.zeros([n * n + 2 * n], "float32")
    bias[:n] = -np.log(max(n - 1, 1))
    bias[2 * n:] = (c["hc_res_diag_init"] * np.eye(n)).reshape(-1)
    init = fluid.initializer
    return fluid.layers.mhc_pre(
        x, n, sinkhorn_iters=c["hc_sinkhorn_iters"], epsilon=c["hc_eps"],
        clamp=(c["mhc_h_res_clamp_min"], c["mhc_h_res_clamp_max"]),
        phi_attr=_matrix(c, role + ".phi"),
        bias_attr=_attr(c, role + ".b", init.NumpyArrayInitializer(bias)),
        alpha_attr=_attr(c, role + ".alpha",
                         init.Constant(c["hc_alpha_init"])))


def gated_delta_net(x, c):
    """The linear-attention mixer over x [B, T, D]: one projection to q, k
    (Hk heads of dk), v and the norm's gate z (Hv heads of dv; stored a key
    head as [q, k, v of its value heads, z of them]) and one to b and a (a
    value head each); a causal depthwise convolution then SiLU over q, k, v
    side by side; beta = sigmoid(b), g = -exp(A_log) * softplus(a +
    dt_bias) in float32; the gated delta rule (q, k l2-normalised, q over
    sqrt(dk)); w * o_hat * silu(z) over each head's dv; the output
    projection. A_log is log(U(0, 16)), dt_bias 1, the norm's weight 1."""
    init = fluid.initializer
    hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    rep = hv // hk
    qkvz = fluid.layers.reshape(
        _linear(x, hk * (2 * dk + 2 * rep * dv), c, "w_qkvz"),
        shape=[0, -1, hk, 2 * dk + 2 * rep * dv])
    q, k, v, z = fluid.layers.split(qkvz, [dk, dk, rep * dv, rep * dv],
                                    dim=-1)
    b, a = fluid.layers.split(
        fluid.layers.reshape(_linear(x, 2 * hv, c, "w_ba"),
                             shape=[0, -1, hk, 2 * rep]),
        2, dim=-1)
    b, a = (fluid.layers.cast(fluid.layers.reshape(t, shape=[0, -1, hv]),
                              "float32") for t in (b, a))
    mixed = fluid.layers.causal_conv1d(
        fluid.layers.concat([fluid.layers.reshape(t, shape=[0, -1, n])
                             for t, n in ((q, hk * dk), (k, hk * dk),
                                          (v, hv * dv))], axis=2),
        c["linear_conv_kernel_dim"], act="silu",
        param_attr=_matrix(c, "conv"))
    q, k, v = fluid.layers.split(mixed, [hk * dk, hk * dk, hv * dv], dim=-1)
    q, k = (fluid.layers.reshape(t, shape=[0, -1, hk, dk]) for t in (q, k))
    v = fluid.layers.reshape(v, shape=[0, -1, hv, dv])
    dt_bias = fluid.layers.create_parameter(
        [hv], "float32", attr=_attr(c, "dt_bias", init.Constant(1.0)))
    a_log = fluid.layers.create_parameter(
        [hv], "float32", attr=_attr(c, "a_log", init.LogUniform(0.0, 16.0)))
    g = fluid.layers.scale(
        fluid.layers.exp(a_log) * fluid.layers.softplus(a + dt_bias),
        scale=-1.0)
    o = fluid.layers.gated_delta_rule(q, k, v, g, fluid.layers.sigmoid(b))
    o = fluid.layers.rms_norm(
        o, epsilon=c["rms_norm_eps"], param_attr=_attr(c, "gated_norm"),
        gate=fluid.layers.reshape(z, shape=[0, -1, hv, dv]))
    return _linear(fluid.layers.reshape(o, shape=[0, -1, hv * dv]),
                   c["hidden_size"], c, "w_out")


def kda(x, c):
    """Kimi Delta Attention (Kimi Linear, arXiv:2510.26692) over x [B, T,
    D] as bailing_hybrid's config switches it (no_kda_lora, kda_safe_gate,
    linear_silu, use_qk_norm), H = num_attention_heads heads of d_k = d_v =
    head_dim: q~, k~, v = SiLU(conv(x W_q)), SiLU(conv(x W_k)), SiLU(conv(x
    W_v)), each a causal depthwise convolution of short_conv_kernel_size
    taps with a filter of its own; a = x W_f + dt_bias, one number a key
    CHANNEL (W_f [D, H d_k], full rank); the log decay g = kda_lower_bound x
    sigmoid(exp(A_log_h) a), in (kda_lower_bound, 0), float32; beta =
    sigmoid(x W_beta), one a head; the delta rule with the decay a channel
    (layers.kda_delta_rule: q and k l2-normalised there, q over sqrt(d_k));
    y = [N_head(o) * sigmoid(x W_g)] W_o, the norm over a head's d_v with one
    weight [d_v] all heads share, the gate a channel (W_g [D, H d_v], full
    rank). Parameters, in this order: wq, conv_q, wk, conv_k, wv, conv_v,
    wf, dt_bias [H d_k], a_log [H], wbeta, o_norm, wg, wo. A_log starts at
    log(U(1, 16)) a head; dt_bias at U(kda_dt_bias_range), a key of the
    config's own ((-1, 0) unless given), from KDA_DT_SEED's stream; the
    norm's weight at 1."""
    import numpy as np
    layers, init = fluid.layers, fluid.initializer
    h, dk, d = c["num_attention_heads"], c["head_dim"], c["hidden_size"]
    taps = c["short_conv_kernel_size"]
    q, k, v = (layers.reshape(layers.causal_conv1d(
        _linear(x, h * dk, c, "w" + name), taps, act="silu",
        param_attr=_matrix(c, "conv_" + name)), shape=[0, -1, h, dk])
        for name in "qkv")
    a = layers.cast(_linear(x, h * dk, c, "wf"), "float32")
    low, high = c.get("kda_dt_bias_range", (-1.0, 0.0))
    dt_bias = layers.create_parameter(
        [h * dk], "float32", attr=_attr(
            c, "dt_bias", init.NumpyArrayInitializer(
                np.random.RandomState(KDA_DT_SEED + c["layer"]).uniform(
                    low, high, h * dk).astype("float32"))))
    a = layers.reshape(a + dt_bias, shape=[0, -1, h, dk])
    a_log = layers.create_parameter(
        [h], "float32", attr=_attr(c, "a_log", init.LogUniform(1.0, 16.0)))
    g = layers.scale(layers.sigmoid(
        layers.elementwise_mul(a, layers.exp(a_log), axis=2)),
        scale=float(c["kda_lower_bound"]))
    beta = layers.sigmoid(layers.cast(_linear(x, h, c, "wbeta"), "float32"))
    o = layers.kda_delta_rule(q, k, v, g, beta)
    o = layers.rms_norm(o, epsilon=c["rms_norm_eps"],
                        param_attr=_attr(c, "o_norm"))
    gate = layers.reshape(layers.sigmoid(_linear(x, h * dk, c, "wg")),
                          shape=[0, -1, h, dk])
    return _linear(layers.reshape(o * gate, shape=[0, -1, h * dk]), d, c,
                   "wo")


def short_conv(x, c):
    """The gated short convolution (Lfm2MoeShortConv) over x [B, T, D]: one
    projection to [B, C, u], D channels each in that order; v = B * u; a
    causal depthwise convolution of conv_L_cache taps over v, no bias and
    no activation; (C * that) through the output projection. Three ops, not
    one with the gates inside the convolution's kernels: on the v5e at [1,
    8192, 2048] a layer's mixer outside its two matmuls takes 1.15 times
    what one pass over its operands would (PERF.md section 6, PR 39). The
    two gate multiplies are named `short_conv` in the table by op type."""
    d = c["hidden_size"]
    b, gate, u = fluid.layers.split(_linear(x, 3 * d, c, "w_in"), 3, dim=-1)
    conv = fluid.layers.causal_conv1d(
        _gated(b, u), c["conv_L_cache"], param_attr=_matrix(c, "conv"))
    return _linear(_gated(gate, conv), d, c, "w_out")


def _gated(gate, x):
    """gate * x, every op the product appends to the block named
    `short_conv` under SCOPE_ATTR."""
    ops = fluid.default_main_program().current_block().ops
    first = len(ops)
    out = gate * x
    for op in ops[first:]:
        op.attrs[SCOPE_ATTR] = "short_conv"
    return out


def differential_attention(x, pos, c):
    """Differential attention (arXiv:2410.05258 as `modeling_phi4flash.py`
    has it) over x [B, T, D]: the H query heads are H / 2 pairs, q = x W_q +
    b_q as [T, H / 2, 2, hd] -> q1, q2; k and v likewise on Hkv / 2 pairs, V
    = [v1; v2] one value of 2 hd a pair; P_j = softmax(q_j k_j^T / sqrt(hd)
    + mask); o = P_1 V - lambda P_2 V with lambda = exp(lq1 . lk1) - exp(lq2
    . lk2) + lambda_init, four learned vectors of hd; RMSNorm over the 2 hd
    (a weight of 2 hd) x (1 - lambda_init); then W_o + b_o. The mask is
    causal, under c["window"] where the layer has one.

    The core is ONE layers.fused_attention at a head of 2 hd: H "heads" (a
    pair's two maps side by side) on Hkv key heads, a map's query and key
    padded from hd to 2 hd with zeros (the scores are the same, and the
    softmax of a map is taken once where four calls at hd would take it
    twice), the pair's value repeated for its two maps. Where c["reads"] is
    "shared" the layer has W_q, b_q, W_o, b_o, lambda and the norm only, and
    reads the keys and values another layer left in c["handed_on"]; the
    layer c["kv_layer"] leaves them there as its own core reads them."""
    layers = fluid.layers
    d, hd = c["hidden_size"], c["head_dim"]
    pairs, kv_pairs = c["num_attention_heads"] // 2, \
        c["num_key_value_heads"] // 2
    group = pairs // kv_pairs
    bias = c["attention_bias"]

    def padded(t):                      # [.., hd] -> [.., 2 hd], zeros after
        return layers.concat([t, layers.scale(t, scale=0.0)], axis=3)

    # a key pair's queries: [kv pair, query pair, map] -> [kv pair, map,
    # query pair], so that map j's queries read key head (kv pair, j)
    q = layers.reshape(_linear(x, 2 * pairs * hd, c, "wq", bias),
                       shape=[0, -1, kv_pairs, group, 2, hd])
    q = padded(layers.reshape(layers.transpose(q, perm=[0, 1, 2, 4, 3, 5]),
                              shape=[0, -1, 2 * pairs, hd]))
    if c["reads"] == "shared":
        k, v = c["handed_on"]["kv"]
    else:
        k = padded(layers.reshape(
            _linear(x, 2 * kv_pairs * hd, c, "wk", bias),
            shape=[0, -1, 2 * kv_pairs, hd]))
        v = layers.reshape(_linear(x, 2 * kv_pairs * hd, c, "wv", bias),
                           shape=[0, -1, kv_pairs, 1, 2 * hd])
        v = layers.reshape(layers.expand(v, expand_times=[1, 1, 1, 2, 1]),
                           shape=[0, -1, 2 * kv_pairs, 2 * hd])
        if c["layer"] == c["kv_layer"]:
            c["handed_on"]["kv"] = (k, v)
    ctx = layers.fused_attention(q, k, v, causal=True, window=c["window"],
                                 scale=hd ** -0.5)
    first, second = layers.split(
        layers.reshape(ctx, shape=[0, -1, kv_pairs, 2, group * 2 * hd]), 2,
        dim=3)
    vectors = [layers.create_parameter(
        [hd], "float32", attr=_attr(c, role, fluid.initializer.Normal(
            0.0, c["lambda_initializer_range"])))
        for role in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")]
    lam = layers.scale(
        layers.exp(layers.reduce_sum(vectors[0] * vectors[1]))
        - layers.exp(layers.reduce_sum(vectors[2] * vectors[3])),
        bias=c["lambda_init"])
    out = first - layers.elementwise_mul(
        second, layers.cast(lam, second.dtype))
    out = layers.rms_norm(
        layers.reshape(out, shape=[0, -1, pairs, 2 * hd]),
        epsilon=c["rms_norm_eps"], param_attr=_attr(c, "subln"))
    return _linear(layers.reshape(
        layers.scale(out, scale=1.0 - c["lambda_init"]),
        shape=[0, -1, pairs * 2 * hd]), d, c, "wo", bias)


def _delta_bias_start(seed, n):
    """n biases of Delta at Mamba's own start: the inverse softplus of
    exp(U(ln 1e-3, ln 1e-1)), from the stream `seed`."""
    import numpy as np
    dt = np.exp(np.random.RandomState(seed).uniform(
        np.log(1e-3), np.log(1e-1), n))
    return (dt + np.log(-np.expm1(-dt))).astype("float32")


def mamba(x, c):
    """A Mamba-1 mixer (arXiv:2312.00752) over x [B, T, D], d_i =
    mamba_expand x D, N = mamba_d_state, R = mamba_dt_rank: [u; z] = x W_in;
    c = SiLU(conv(u) + b_c), a causal depthwise convolution of mamba_d_conv
    taps; [r; B; C] = c W_x; Delta = softplus(r W_Delta + b_Delta) in
    float32; A = -exp(A_log); y = selective_scan(c, Delta, A, B, C, D); (y *
    SiLU(z)) W_out. The layer c["memory_layer"] leaves y, BEFORE the gate,
    in c["handed_on"] for the gated memory units. A_log starts at log(1 ..
    N) a channel, D at 1, b_Delta at the inverse softplus of exp(U(ln 1e-3,
    ln 1e-1)) from MAMBA_DT_SEED's stream, W_Delta uniform within R^-0.5."""
    import numpy as np
    layers, init = fluid.layers, fluid.initializer
    d, n, rank = c["hidden_size"], c["mamba_d_state"], c["mamba_dt_rank"]
    di = c["mamba_expand"] * d
    u, z = layers.split(_linear(x, 2 * di, c, "w_in"), 2, dim=-1)
    u = layers.causal_conv1d(u, c["mamba_d_conv"], param_attr=_matrix(
        c, "conv"), act=None if c["conv_bias"] else "silu")
    if c["conv_bias"]:
        u = layers.swish(layers.elementwise_add(
            u, layers.create_parameter(
                [di], "float32",
                attr=_attr(c, "conv.bias", init.Constant(0.0))), axis=2))
    r, b, cc = layers.split(_linear(u, rank + 2 * n, c, "w_x"),
                            [rank, n, n], dim=-1)
    delta = layers.softplus(layers.elementwise_add(
        layers.cast(layers.fc(
            input=r, size=di, bias_attr=False, num_flatten_dims=2,
            param_attr=_attr(c, "w_dt", init.Uniform(-rank ** -0.5,
                                                      rank ** -0.5))),
            "float32"),
        layers.create_parameter(
            [di], "float32", attr=_attr(c, "dt_bias", init.NumpyArrayInitializer(
                _delta_bias_start(MAMBA_DT_SEED + c["layer"], di)))),
        axis=2))
    a_log = layers.create_parameter(
        [di, n], "float32", attr=_attr(c, "a_log", init.NumpyArrayInitializer(
            np.log(np.tile(np.arange(1, n + 1, dtype="float32"), (di, 1))))))
    skip = layers.create_parameter(
        [di], "float32", attr=_attr(c, "d", init.Constant(1.0)))
    y = layers.selective_scan(
        u, delta, layers.scale(layers.exp(a_log), scale=-1.0),
        layers.cast(b, "float32"), layers.cast(cc, "float32"), skip)
    if c["layer"] == c["memory_layer"]:
        c["handed_on"]["memory"] = y
    return _linear(y * layers.swish(z), d, c, "w_out")


def mamba2(x, c):
    """A Mamba-2 mixer (arXiv:2405.21060 as granitemoehybrid has it) over x
    [B, T, D]: H = mamba_n_heads heads of P = mamba_d_head, d_i = H P, N =
    mamba_d_state, G = mamba_n_groups groups of B and C (head h reads group
    h // (H / G)). [z; xBC; dt] = x W_in (d_i + (d_i + 2 G N) + H
    columns, no bias); xBC' = SiLU(conv(xBC) + b_c), a causal depthwise
    convolution of mamba_d_conv taps over x, B and C side by side; [x; B; C]
    = xBC', B and C [G, N] a token; Delta = softplus(dt + dt_bias) in
    float32; A = -exp(A_log) a
    head; y = ssd_scan(x, Delta, A, B, C, D); o = RMSNorm(y * SiLU(z)) over
    each group's d_i / G channels under one weight of d_i (one group: over
    all d_i at once), the gate FIRST (rms_norm(gate=) norms first);
    o W_out. Parameters: w_in, conv, [conv.bias], dt_bias, a_log, d,
    gated_norm, w_out. A_log starts at log(1 .. H) a head, D at 1, dt_bias
    at the inverse softplus of exp(U(ln 1e-3, ln 1e-1)) from
    MAMBA2_DT_SEED's stream (the family's modeling file starts it at 1,
    where A = -64 forgets within a token)."""
    import numpy as np
    layers, init = fluid.layers, fluid.initializer
    d, n = c["hidden_size"], c["mamba_d_state"]
    h, p, g = c["mamba_n_heads"], c["mamba_d_head"], c["mamba_n_groups"]
    di = h * p
    z, xbc, dt = layers.split(_linear(x, 2 * di + 2 * g * n + h, c, "w_in"),
                              [di, di + 2 * g * n, h], dim=-1)
    xbc = layers.causal_conv1d(
        xbc, c["mamba_d_conv"], param_attr=_matrix(c, "conv"),
        act=None if c["mamba_conv_bias"] else "silu")
    if c["mamba_conv_bias"]:
        xbc = layers.swish(layers.elementwise_add(
            xbc, layers.create_parameter(
                [di + 2 * g * n], "float32",
                attr=_attr(c, "conv.bias", init.Constant(0.0))), axis=2))
    u, b, cc = layers.split(xbc, [di, g * n, g * n], dim=-1)
    if g > 1:       # one group stays [B, T, N], the op it always was
        b, cc = (layers.reshape(t, shape=[0, -1, g, n]) for t in (b, cc))
    delta = layers.softplus(layers.elementwise_add(
        layers.cast(dt, "float32"),
        layers.create_parameter(
            [h], "float32", attr=_attr(c, "dt_bias", init.NumpyArrayInitializer(
                _delta_bias_start(MAMBA2_DT_SEED + c["layer"], h)))),
        axis=2))
    a_log = layers.create_parameter(
        [h], "float32", attr=_attr(c, "a_log", init.NumpyArrayInitializer(
            np.log(np.arange(1, h + 1, dtype="float32")))))
    skip = layers.create_parameter(
        [h], "float32", attr=_attr(c, "d", init.Constant(1.0)))
    y = layers.ssd_scan(
        layers.reshape(u, shape=[0, -1, h, p]), delta,
        layers.scale(layers.exp(a_log), scale=-1.0), b, cc, skip)
    gated = layers.reshape(y, shape=[0, -1, di]) * layers.swish(z)
    if g > 1:
        normed = layers.reshape(layers.rms_norm(
            layers.reshape(gated, shape=[0, -1, g, di // g]),
            epsilon=c["rms_norm_eps"], param_attr=_attr(c, "gated_norm"),
            begin_scale_axis=-2), shape=[0, -1, di])
    else:
        normed = layers.rms_norm(gated, epsilon=c["rms_norm_eps"],
                                 param_attr=_attr(c, "gated_norm"))
    return _linear(normed, d, c, "w_out")


def gated_memory_unit(x, c):
    """(SiLU(x W_1) * m) W_2 over x [B, T, D], m [B, T, d_i] the scan
    output that layer c["memory_layer"] left in c["handed_on"]."""
    gate = fluid.layers.swish(_linear(
        x, c["mamba_expand"] * c["hidden_size"], c, "w_in"))
    return _linear(gate * c["handed_on"]["memory"], c["hidden_size"], c,
                   "w_out")


def _relu2_mlp(x, width, c, role=""):
    """nemotron_h's MLP, two matrices: relu(x W_up)^2 W_down."""
    return _linear(fluid.layers.square(fluid.layers.relu(
        _linear(x, width, c, role + "w_up"))), c["hidden_size"], c,
        role + "w_down")


def _swiglu(x, width, c, role=""):
    if c["mlp_gate_up_fused"]:
        # one first matrix, [gate; value]
        gate, up = fluid.layers.split(
            _linear(x, 2 * width, c, role + "w_gate_up"), 2, dim=-1)
        gate = fluid.layers.swish(gate)
    else:
        gate = fluid.layers.swish(_linear(x, width, c, role + "w_gate"))
        up = _linear(x, width, c, role + "w_up")
    return _linear(gate * up, c["hidden_size"], c, role + "w_down")


def feed_forward(x, c, router_input=None):
    """(out, aux) of one layer's FFN on x [B, T, D]: routed experts give
    aux = (balance_loss, z_loss, expert_load), the dense SwiGLU None.
    `router_input` is what the router reads where it is not x. A shared
    expert (shared_expert_intermediate_size), a SwiGLU every token passes
    scaled by sigmoid(x w_s) (shared_expert_gate; without it, DeepSeek-V3's
    n_shared_experts, as it is), is added to the routed experts' output; in
    a share of a layer it is every chip's own, computed once. c["ffn"] is the
    layer's kind (absent, a config seen outside a stack: experts where it
    has any): a model with num_dense_layers has dense layers at
    dense_intermediate_size before its expert layers. An expert bias is
    drawn at expert_bias_initializer_range from EXPERT_BIAS_SEED's stream
    and training does not move it. With moe_latent_size = L > 0 (nemotron_h's
    LatentMoE) the routed experts work on u = x W_dn [L] and their sum goes
    through W_up [L, D] (`latent_down`, `latent_up`); the router and the
    shared expert read x. Under hybrid_override_pattern every FFN is
    ungated, relu(x W_up)^2 W_down: the experts have no w_gate, the shared
    expert and the dense MLP two matrices."""
    if c.get("ffn", "experts" if c["num_experts"] else "dense") == "experts":
        routed_in = x
        if c["moe_latent_size"]:
            routed_in, router_input = _linear(
                x, c["moe_latent_size"], c, "latent_down"), x
        bias = None
        if c["use_expert_bias"]:
            bias = fluid.ParamAttr(initializer=fluid.initializer.Normal(
                0.0, c["expert_bias_initializer_range"],
                seed=EXPERT_BIAS_SEED)
                if c["expert_bias_initializer_range"] else None)
        out, balance, z, load = fluid.layers.moe_ffn(
            routed_in, num_experts=c["num_experts"],
            d_expert=c["intermediate_size"],
            top_k=c["num_experts_per_tok"],
            norm_topk_prob=c["norm_topk_prob"],
            param_attr=_matrix(c, "experts"), router_input=router_input,
            activation=c["hidden_act"],
            experts_held=c["experts_held"], first_expert=c["first_expert"],
            scoring=c["router_scoring"],
            expert_bias_attr=bias,
            routed_scaling_factor=c["routed_scaling_factor"],
            norm_epsilon=c["router_renorm_epsilon"], gated=c["ffn_gated"],
            n_group=c["n_group"] if c["group_limited"] else 1,
            topk_group=c["topk_group"] if c["group_limited"] else 1)
        if c["moe_latent_size"]:
            out = _linear(out, c["hidden_size"], c, "latent_up")
        if c["shared_expert_intermediate_size"]:
            shared = (_swiglu if c["ffn_gated"] else _relu2_mlp)(
                x, c["shared_expert_intermediate_size"], c, "shared_expert.")
            if c["shared_expert_gate"]:
                shared = shared * fluid.layers.sigmoid(
                    _linear(x, 1, c, "shared_expert.gate"))
            out = out + shared
        return out, (balance, z, load)
    return (_swiglu if c["ffn_gated"] else _relu2_mlp)(
        x, c["dense_intermediate_size"], c), None


def _count_layer(c, mixer, module="trunk"):
    """One count a layer built, by what the model puts around its ops and
    no op can observe (the ops' own counters have the rest: heads, widths,
    paths). A looped model builds a layer once and counts it once."""
    from ..observability.registry import REGISTRY
    attention = mixer == "attention"
    REGISTRY.counter(
        "ptpu_causal_lm_layers_total",
        "decoder layers causal_lm built, by mixer, the channels of a head "
        "its rotary turns (0: none), whether a sigmoid gate multiplies the "
        "attention's output, the taps of the convolution before a gated "
        "delta rule or of a short_conv mixer's own (0: none), the FFN's "
        "kind (dense, or routed experts), the width of the shared expert "
        "beside the routed ones (0: none), whether each branch is normed "
        "going out as well as going in (a sandwich), the module the layer "
        "belongs to (trunk, or mtp: a multi-token-prediction module's), "
        "whose state the mixer reads (own: its input's alone; shared: the "
        "memory or the keys and values another layer handed on) and whether "
        "the attention is differential; and, under hybrid_override_pattern "
        "alone (the other models' layers count under the labels they always "
        "had), the layer's branches (1: a mixer or an FFN; 2: both), the "
        "width of the latent space its routed experts work in (0: the "
        "model's own) and whether its FFN is gated (mixer none: a layer "
        "that is an FFN alone; ffn none: a mixer alone); and, for a config "
        "with a geometry by layer alone (rope_parameters, "
        "num_attention_heads_per_layer), the query and key/value heads the "
        "layer holds, its window (0: none) and its rotary parameters' kind "
        "(default, yarn, none), rotary_dim then the layer's own; gate is "
        "per_head where the gate is a scalar a head; and, for a config with "
        "objective block_diffusion alone, mask block_diffusion and the "
        "block_length of the mask its attention runs under; and, for a "
        "layer of routed experts whose router's choice is limited to groups "
        "alone, `groups` (the runs of neighbouring experts) and "
        "`kept_groups` (those a token's top k is taken inside); mixer kda: "
        "a delta rule whose decay is a key channel's"
    ).inc(mixer=mixer, module=module, reads=c["reads"],
          **({} if c["block_diffusion"] is None else dict(
              mask="block_diffusion",
              block_length=str(c["block_diffusion"]["block_length"]))),
          **({} if not (c["group_limited"] and c["ffn"] == "experts")
             else dict(groups=str(c["n_group"]),
                       kept_groups=str(c["topk_group"]))),
          **({} if not c["geometry_by_layer"] else dict(
              heads=str(c["num_attention_heads"] if attention else 0),
              kv_heads=str(c["num_key_value_heads"] if attention else 0),
              window=str(c["window"] or 0),
              rope=c.get("rope_type", "default")
              if attention and c["rope_theta"] is not None else "none")),
          **({} if c.get("hybrid_override_pattern") is None else dict(
              branches=str(2 - ("none" in (mixer, c["ffn"]))),
              latent=str(c["moe_latent_size"] if c["ffn"] == "experts"
                         else 0),
              gated=str(bool(c["ffn_gated"])).lower())),
          differential=str(bool(attention
                                and c["differential_attention"])).lower(),
          rotary_dim=str(c["rotary_dim"] if attention
                         and c["rope_theta"] is not None else 0),
          gate="per_head" if attention and c["attention_gate"] == "per_head"
          else str(bool(attention and c["attention_gate"])).lower(),
          conv=str(0 if attention or mixer in ("gmu", "none")
                   else c["short_conv_kernel_size"] if mixer == "kda"
                   else c["conv_L_cache"]
                   if mixer == "short_conv" else c["mamba_d_conv"]
                   if mixer in ("mamba", "mamba2")
                   else c["linear_conv_kernel_dim"]),
          ffn=c["ffn"],
          shared=str(c["shared_expert_intermediate_size"]
                     if c["ffn"] == "experts" else 0),
          sandwich=str(bool(c["sandwich_norm"])).lower())


def exit_distribution(states, c):
    """(p, log p) [B, passes, T] of the exit gate on the passes' normed
    states, each [B, T, D]: lambda_t = sigmoid(h_t w_g + b_g) a token, p_t
    = lambda_t prod_(j<t) (1 - lambda_j) and the last pass takes what is
    left, so the passes' shares sum to 1. In float32 and in logarithms: log
    lambda = logsigmoid(z), log(1 - lambda) = logsigmoid(-z), so a
    saturated gate gives p = 0 and p log p = 0, not 0 x inf."""
    layers = fluid.layers
    w = layers.create_parameter(
        [c["hidden_size"]], "float32", attr=_matrix(c, "exit_gate.w"))
    b = layers.create_parameter(
        [1], "float32",
        attr=_attr(c, "exit_gate.b", fluid.initializer.Constant(0.0)))
    left, log_p = None, []              # log prod_(j<t) (1 - lambda_j)
    for h in states[:-1]:
        z = layers.reduce_sum(layers.elementwise_mul(
            layers.cast(h, "float32"), w, axis=2), dim=-1)
        z = layers.reshape(layers.elementwise_add(z, b), shape=[0, 1, -1])
        exits = layers.logsigmoid(z)
        stays = layers.logsigmoid(layers.scale(z, scale=-1.0))
        log_p.append(exits if left is None else exits + left)
        left = stays if left is None else left + stays
    log_p = layers.concat(log_p + [left], axis=1)
    return layers.exp(log_p), log_p


def causal_lm(cfg, seq_len, extras=None, recompute=True):
    """Build the training graph in the current program guard. Feeds: `ids`
    [B, T] token ids, `pos` [B, T] their positions, `labels` [B, T, 1] the
    next token at every position and, with a multi-token-prediction module,
    a third, `labels_next` [B, T, 1], the token after that; under objective
    block_diffusion `ids`, `noisy_ids`, `pos` and `loss_weight` [B, T]
    (module docstring: 2 T rows through the trunk, the logits [B, T, V] the
    noised copy's, the loss the weighted mean). Returns (loss,
    logits [B, T, V], expert_load): the loss is the mean cross-entropy a
    position plus router_aux_loss_coef x the layers' mean balance loss plus
    router_z_loss_coef x their mean z loss (neither term is built where
    both coefficients are 0); expert_load [E] int32 sums the layers'
    assignment counts (None without experts), the module's layer among
    them. With the module the loss has two terms, L_main + mtp_loss_weight
    x L_mtp, L_mtp the mean cross-entropy of the module's logits (through
    the trunk's own head) against `labels_next`; `logits` are the trunk's,
    and `extras` gets `main_loss`, `mtp_loss`, `mtp_logits` [B, T, V] and
    `mtp_input` [B, T, D], what the module's layer reads.

    With total_ut_steps = P > 1 the layers and the final norm are built
    once, as the sub-block of one loop op (a StaticRNN with no step input:
    one lax.scan of P trips whose carry is the normed state), and run P
    times over the same weights, which the loop closes over; a weight's
    gradient is the sum over its P uses through the scan's transpose. With
    `recompute` the loop's body runs under jax.checkpoint: a pass keeps the
    state it started from and, a layer, the attention kernel's outputs, the
    down projection's result and the norms' row statistics (what the loop
    finds cheap to keep and dear to replay, ops/control_ops.py
    keeps_across_passes), and the
    backward pass replays the rest of its forward, so what crosses from the
    forward to the backward pass is P states and 2 P x layers arrays of the
    state's shape, not P x layers' worth of activations. `logits` are the last pass's. With
    exit_gate every pass's state goes through the head and the loss is
    the mean a position of sum_t p_t CE_t - exit_entropy_coef x
    H(p), p the exit distribution; without it only the last pass has a
    loss. A dict given as `extras` is filled with `pass_logits`, the list of
    the passes' logits [B, T, V] that have a loss, and `exit_p` [B, P, T]."""
    c = resolve(cfg)
    layers, passes = fluid.layers, c["total_ut_steps"]
    objective = c["block_diffusion"]
    ids = layers.data("ids", [seq_len], dtype="int64")
    if objective is None:
        pos = layers.data("pos", [seq_len], dtype="int64")
        labels = layers.data("labels", [seq_len, 1], dtype="int64")
        tokens = ids
    else:
        if seq_len % objective["block_length"]:
            raise ValueError(
                "objective block_diffusion trains whole blocks: block_length "
                "%d does not divide the sequence of %d"
                % (objective["block_length"], seq_len))
        noisy_ids = layers.data("noisy_ids", [seq_len], dtype="int64")
        pos = layers.data("pos", [seq_len], dtype="int64")
        loss_weight = layers.data("loss_weight", [seq_len], dtype="float32")
        # 2 T rows through the trunk: the noised copy's, then the clean
        # copy's, one lookup of one parameter, both copies of token i at
        # position i; the label of a noised row is the clean id at its own
        # position
        tokens = layers.concat([noisy_ids, ids], axis=1)
        pos = layers.concat([pos, pos], axis=1)
        labels = ids
        copies = (objective["block_length"], seq_len)
    trunk, mtp = c["num_hidden_layers"], c["mtp_layers"]

    def embed(tokens):
        # asked for again (the module's lookup of the next tokens), the
        # name gives the parameter the first lookup made
        return layers.embedding(
            tokens, size=[c["vocab_size"], c["hidden_size"]],
            param_attr=_attr(c, "embedding", fluid.initializer.Normal(
                0.0, c.get("embedding_initializer_range",
                           c["initializer_range"]))))

    h = embed(tokens)
    # granitemoehybrid's scalars on the main path, each an op only where it
    # is not 1: embedding_multiplier here, residual_multiplier on what each
    # branch adds to the stream, logits_scaling under the logits
    # (attention_multiplier is the scale the attention's core is given)
    if c["embedding_multiplier"] != 1:
        h = layers.scale(h, scale=float(c["embedding_multiplier"]))
    branch = float(c["residual_multiplier"])
    ops = fluid.default_main_program().global_block().ops
    module_ops = []             # [first, end) runs of the module's ops
    # One pass: the layers, then the final norm; a looped model builds it
    # as the sub-block of a loop op. A layer is h + mixer(N1(h)), then that
    # + FFN(N3(.)); with sandwich_norm each branch passes a norm of its own
    # on the way out too: a = h + N2(mixer(N1(h))), a + N4(FFN(N3(a))).
    # Written out here and not in a function of its own: jax records the
    # Python stack with every equation a rule's shape inference traces, and
    # each frame more is paid for by every op of every layer (PERF.md 6).
    aux = []
    loop = layers.StaticRNN(steps=passes, recompute=recompute) \
        if passes > 1 else None
    with loop.step() if loop else contextlib.nullcontext():
        if loop:
            h = state = loop.memory(init=h)
        # hc_mult = n > 1 streams (arXiv:2512.24880): the layers carry X [B,
        # T, n * D], the embedding n times; a sub-layer reads h = sum_i
        # H_pre[i] X[i] and X' = H_res X + H_post y takes its output; the
        # streams' sum is read out
        streams = c["hc_mult"]
        if streams > 1:
            h = layers.mhc_expand(h, streams)
        # what one layer leaves for later layers to read (`memory`, `kv`)
        c["handed_on"] = {}
        for i in range(trunk + mtp):
            cl, mixer = _layer(c, i), c["mixer_layers"][i]
            if objective is not None:
                # every layer's core under the mask; the last layer takes
                # the noised copy's rows behind it (they alone carry a loss)
                cl["copies"] = copies
                cl["noised_rows"] = seq_len if i == trunk - 1 else None
                _count_rows(seq_len, last=i == trunk - 1)
            if i == trunk:
                # the multi-token-prediction module: the trunk's state is
                # kept, normed, for the trunk's head, and the module's layer
                # (the layer body below, at index `trunk`) reads W_eh
                # [N_e(Emb(t_(i+1))); N_h(that)]
                trunk_state = _norm(h, c, "final_norm")
                module_ops.append(len(ops))
                h = mtp_input = _linear(layers.concat([
                    _norm(embed(layers.reshape(labels, shape=[0, seq_len])),
                          cl, "enorm"),
                    _norm(trunk_state, cl, "hnorm")], axis=2),
                    c["hidden_size"], cl, "eh_proj")
            _count_layer(cl, mixer, "mtp" if i >= trunk else "trunk")
            if "none" in (mixer, cl["ffn"]):
                # hybrid_override_pattern's layer: ONE branch behind one
                # norm (`layer_<i>.norm`), h + f(N(h)), f a mixer or an FFN
                a = _norm(h, cl, "norm")
                if mixer != "none":
                    h = h + (attention(a, pos, cl) if mixer == "attention"
                             else mamba2(a, cl))
                    continue
                out, layer_aux = feed_forward(a, cl)
                h = h + out
                if layer_aux is not None:
                    aux.append(layer_aux)
                continue
            if streams > 1:
                read, coef, h = hyper_connection(h, cl, "attn_hc")
            a = _norm(read if streams > 1 else h, cl, "input_norm")
            mixed = (latent_attention if c["latent"]
                     else differential_attention
                     if c["differential_attention"] else attention)(
                a, pos, cl) if mixer == "attention" \
                else short_conv(a, cl) if mixer == "short_conv" \
                else mamba(a, cl) if mixer == "mamba" \
                else mamba2(a, cl) if mixer == "mamba2" \
                else gated_memory_unit(a, cl) if mixer == "gmu" \
                else kda(a, cl) if mixer == "kda" \
                else gated_delta_net(a, cl)
            if c["sandwich_norm"]:
                mixed = _norm(mixed, cl, "mixer_out_norm")
            if branch != 1:
                mixed = layers.scale(mixed, scale=branch)
            if streams > 1:
                h = layers.mhc_post(h, mixed, coef, streams)
                read, coef, h = hyper_connection(h, cl, "ffn_hc")
            elif cl.get("noised_rows"):
                h = layers.crop(h, shape=[-1, seq_len, -1]) + mixed
            else:
                h = h + mixed
            out, layer_aux = feed_forward(
                _norm(read if streams > 1 else h, cl, "post_attention_norm"),
                cl, router_input=a if c["router_input"] == "pre_attention"
                else None)
            if c["sandwich_norm"]:
                out = _norm(out, cl, "ffn_out_norm")
            if branch != 1:
                out = layers.scale(out, scale=branch)
            h = layers.mhc_post(h, out, coef, streams) if streams > 1 \
                else h + out
            if layer_aux is not None:
                aux.append(layer_aux)
        if streams > 1:
            h = layers.mhc_reduce(h, streams)
        if mtp:
            mtp_state = _norm(h, cl, "shared_head.norm")
            module_ops.append(len(ops))
            h = trunk_state
        else:
            h = _norm(h, c, "final_norm")
        if loop:
            loop.update_memory(state, h)
            loop.output(h)
    if loop:
        fluid.default_main_program().global_block().ops[-1].attrs[
            PASS_ATTR] = "1-%d" % passes
        states = [layers.reshape(one, shape=[0, seq_len, c["hidden_size"]])
                  for one in layers.split(loop(), passes, dim=1)]
    else:
        states = [h]
    _count_passes(c)
    _count_rope_tables(c)

    _count_head(c)
    tied = fluid.default_main_program().global_block().var("embedding") \
        if c["tie_word_embeddings"] else None

    def head(state, labels=labels):
        # tied: h E^T on the embedding's own [V, D] parameter; its gradient
        # is the lookup's scatter-add plus this matmul's, summed where
        # core/backward.py accumulates a variable's several uses. Untied and
        # run twice (the module's pass), the name gives the one parameter
        logits = layers.matmul(state, tied, transpose_y=True) \
            if tied is not None else _linear(state, c["vocab_size"], c,
                                             "head")
        if c["logits_scaling"] != 1:
            logits = layers.scale(logits, scale=1.0 / c["logits_scaling"])
        return logits, layers.softmax_with_cross_entropy(
            logits=layers.reshape(logits, shape=[-1, c["vocab_size"]]),
            label=layers.reshape(labels, shape=[-1, 1]))

    if c["exit_gate"]:
        # a pass's head and its loss at a time: one [B x T, V] array of
        # logits is alive, not P of them
        p, log_p = exit_distribution(states, c)
        pass_logits, costs = zip(*(head(state) for state in states))
        cost = layers.concat([layers.reshape(cost, shape=[-1, 1, seq_len])
                              for cost in costs], axis=1)
        # sum_t p_t CE_t - beta H(p), H(p) = -sum_t p_t log p_t
        loss = layers.mean(layers.reduce_sum(
            p * (cost + layers.scale(log_p, scale=c["exit_entropy_coef"])),
            dim=1))
        found = {"exit_p": p}
    else:
        found, (pass_logits, costs) = {}, zip(head(states[-1]))
        # block diffusion's: (1 / T) sum_i w_i CE(logits_i, x_i), w_i = 1 /
        # t a masked position and 0 elsewhere, as fed
        loss = layers.mean(costs[0] if objective is None else costs[0]
                           * layers.reshape(loss_weight, shape=[-1, 1]))
        if objective is not None:
            _count_rows(seq_len, head=True)
    if mtp:
        # L_main + lambda L_mtp: the module's state through the trunk's
        # head, against the tokens two ahead
        module_ops.append(len(ops))
        mtp_logits, mtp_cost = head(
            mtp_state, layers.data("labels_next", [seq_len, 1],
                                   dtype="int64"))
        mtp_loss = layers.mean(mtp_cost)
        module_ops.append(len(ops))
        for first, end in zip(module_ops[::2], module_ops[1::2]):
            for op in ops[first:end]:
                op.attrs[ROLE_ATTR] = "mtp.0"
        found.update(main_loss=loss, mtp_loss=mtp_loss,
                     mtp_logits=mtp_logits, mtp_input=mtp_input)
        loss = loss + layers.scale(mtp_loss, scale=c["mtp_loss_weight"])
        _count_module(c)
    logits = pass_logits[-1]
    if extras is not None:
        extras.update(found, pass_logits=list(pass_logits))
    load = None
    if aux and (c["router_aux_loss_coef"] or c["router_z_loss_coef"]):
        balance, z, load = (layers.sums(list(terms)) for terms in zip(*aux))
        loss = loss + balance * (c["router_aux_loss_coef"] / len(aux)) \
            + z * (c["router_z_loss_coef"] / len(aux))
    elif aux:
        load = layers.sums([terms[2] for terms in aux])
    return loss, logits, load


def _count_rows(seq_len, last=False, head=False):
    """The rows of one sequence that one layer's parts (or the head) are
    built over under objective block_diffusion: the attention over both
    copies, the FFN over both but in the last layer, the head over the
    noised copy's alone."""
    from ..observability.registry import REGISTRY
    rows = REGISTRY.counter(
        "ptpu_causal_lm_rows_total",
        "rows of ONE sequence (a step's are the batch's times as many) that "
        "causal_lm built each part of each layer over under objective "
        "block_diffusion, by part (attention: the projections into the core "
        "and the core; ffn: what lies behind the core, W_o, the residual "
        "and the FFN; head: the final norm, the head and the loss) and copy "
        "(noised, clean): the last layer's ffn and the head have no clean "
        "rows. A next-token model builds every part over its T rows and "
        "counts nothing here")
    if head:
        rows.inc(seq_len, part="head", copy="noised")
        return
    for copy in ("noised", "clean"):
        rows.inc(seq_len, part="attention", copy=copy)
        if copy == "noised" or not last:
            rows.inc(seq_len, part="ffn", copy=copy)


def _count_head(c):
    from ..observability.registry import REGISTRY
    REGISTRY.counter(
        "ptpu_causal_lm_heads_total",
        "models causal_lm built, by whether the output head reads the "
        "embedding's parameter (tied) or has a matrix of its own; a head is "
        "one parameter and may run more than once a step (a looped model's "
        "passes, a multi-token-prediction module's pass: "
        "ptpu_causal_lm_mtp_modules_total)"
    ).inc(tied=str(bool(c["tie_word_embeddings"])).lower())


def _count_module(c):
    from ..observability.registry import REGISTRY
    REGISTRY.counter(
        "ptpu_causal_lm_mtp_modules_total",
        "multi-token-prediction modules causal_lm built, by depth (the "
        "tokens ahead of the next one a module predicts: 1 is t_(i+2)), "
        "whether its embedding and its head are the trunk's own parameters, "
        "and lambda, the weight of its loss term"
    ).inc(depth="1", shared_embedding="true", shared_head="true",
          loss_weight="%g" % c["mtp_loss_weight"])


def _count_rope_tables(c):
    from ..observability.registry import REGISTRY
    tables = REGISTRY.counter(
        "ptpu_rope_tables_total",
        "rotary parameter sets `resolve` made for the models causal_lm "
        "built, by kind (yarn: a frequency table made on the host; default: "
        "theta^(-2i/R), the op's own, counted where rope_parameters names "
        "it) and the channels of a head it turns: one a program under "
        "rope_scaling, one a KIND of layer under rope_parameters, so a "
        "table made a layer shows")
    for kind, rotary_dim in c["rope_tables"]:
        tables.inc(kind=kind, rotary_dim=str(rotary_dim))


def _count_passes(c):
    from ..observability.registry import REGISTRY
    REGISTRY.counter(
        "ptpu_layer_passes_total",
        "models causal_lm built, by the times the stack of layers runs over "
        "the same weights, the layers in it and the form the passes have in "
        "the program (scan: one loop op; none: one pass, no loop)"
    ).inc(passes=str(c["total_ut_steps"]),
          layers=str(c["num_hidden_layers"]),
          form="scan" if c["total_ut_steps"] > 1 else "none")


def build_train(cfg, seq_len, learning_rate=4e-4, beta1=0.9, beta2=0.95,
                epsilon=1e-8, clip_norm=1.0, recompute=True, extras=None):
    """causal_lm + Adam under global-norm gradient clipping (clip_norm None:
    no clipping). Returns (loss, logits, expert_load). `recompute` and
    `extras` are causal_lm's: a looped model replays each pass in the
    backward pass unless told to keep every activation."""
    loss, logits, load = causal_lm(cfg, seq_len, extras=extras,
                                   recompute=recompute)
    if clip_norm is not None:
        fluid.clip.set_gradient_clip(
            fluid.clip.GradientClipByGlobalNorm(clip_norm=clip_norm))
    fluid.optimizer.Adam(learning_rate=learning_rate, beta1=beta1,
                         beta2=beta2, epsilon=epsilon).minimize(loss)
    return loss, logits, load
