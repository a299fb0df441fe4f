"""Names the training step gives its device work, in one place.

``jax.named_scope`` and ``pallas_call(name=)`` exist at trace and compile
time only: they end up in each HLO instruction's ``op_name`` metadata and
in a Mosaic kernel's name, which is what a ``jax.profiler`` trace of a
step shows (docs/timeline.md, "Reading a device trace of a step"). The
benchmark's ``benchmark/phase_names.json`` and the docs quote these
strings; ``tests/test_scopes.py`` holds the three together.
"""

# Scopes: a path component of the ``op_name``, forward and (through the
# name stack JAX keeps for the transpose) backward.
REDUCE = "hvd_reduce"        # optim.py core_update: the gradient reduction
PACK = "pack"                # fusion.fuse:   hvd_reduce/pack
UNPACK = "unpack"            # fusion.unfuse: hvd_reduce/unpack
REDUCE_PACK = REDUCE + "/" + PACK
REDUCE_UNPACK = REDUCE + "/" + UNPACK
UPDATE = "hvd_update"        # optim.py core_update: the inner optax update
LM_HEAD = "hvd_lm_head"      # models/gpt.py, bert.py, looplm.py: vocabulary matmul
# models/looplm.py: the exit gate, the exit distribution, its entropy and
# the weighting of the exits' losses
LOOP_EXIT = "hvd_loop_exit"
# parallel/moe.py, the held-experts path: the router (scores by a softmax
# over the experts, or by a sigmoid with a selection bias), top-k, the
# sort of the routes by expert and the weighted combine; the grouped
# matmuls of the routed experts; the shared expert every token meets
MOE_ROUTE = "hvd_moe_route"
MOE_EXPERTS = "hvd_moe_experts"
MOE_SHARED = "hvd_moe_shared"
# ops/linear_attention.py: the gated delta-rule recurrence, chunked
KDA = "hvd_kda"
# ops/linear_attention.py gated_delta_attention: the same recurrence with
# one decay scalar a head (Gated DeltaNet), chunked. "hvd_kda" is no part
# of the string: a reader of one recurrence does not find the other
GDN = "hvd_gdn"
# ops/short_conv.py: the gated short convolution's two gates and its taps,
# not the two projections around them
SHORT_CONV = "hvd_short_conv"
# ops/ssd.py: the selective state-space recurrence with a scalar decay a
# head (Mamba-2's SSD), chunked: the step size, the decays, the chunks'
# pair matrices and states, the carry across chunks and the D skip; not
# the convolution, the gated norm and the projections around it
SSD = "hvd_ssd"
# models/*.py, the parts of a block, one vocabulary for every family
# (docs/timeline.md). A token mixer's kernel (flash attention, the KDA
# recurrence, the gated short convolution) lies outside MIXER_PROJ, and
# the norms are named where a layer calls them, never inside a norm's
# class: a mixer's own q / k / o norms count under the mixer, once.
MIXER_PROJ = "hvd_mixer_proj"   # a mixer's projections, gates and norms
ROPE = "hvd_rope"            # ops/rope.py rotate(): under MIXER_PROJ
MLP = "hvd_mlp"              # the dense feed-forward (no expert layer's)
NORM = "hvd_norm"            # the block-level norms and the final norm
EMBED = "hvd_embed"          # the token (and position) embedding lookup
LOSS = "hvd_loss"            # models/looplm.py head_losses: after LM_HEAD
# models/sdar.py sdar_loss: block-diffusion training's noise (a rate a
# block, a mask a token, drawn on the device from the batch's own seeds)
# and the assembly of [noisy ; clean] with its positions
BD_NOISE = "hvd_bd_noise"

# Pallas kernels: the ``name=`` of each ``pallas_call``. FLASH_DKV is the
# whole flash backward: the dk/dv call also gives dq. FLASH_DQ is carried
# by no call since then; it stays because the benchmark's data file
# quotes FLASH_KERNELS (its ``flash_dq_ms`` reads 0.000).
FLASH_FWD = "hvd_flash_fwd"
FLASH_DQ = "hvd_flash_dq"
FLASH_DKV = "hvd_flash_dkv"
# ops/flash_attention.py under SlidingWindowMask: the same two bodies
# over a window's band, named apart so that a trace tells a window call
# from a full one (none of them holds a FLASH_KERNELS name)
SWA_FWD = "hvd_swa_fwd"
SWA_BWD = "hvd_swa_bwd"
SCALE = "hvd_scale"
ADASUM_DOT_NORMS = "hvd_adasum_dot_norms"
ADASUM_COMBINE = "hvd_adasum_combine"
INT8_QUANTIZE = "hvd_int8_quantize"
INT8_QUANTIZE_SR = "hvd_int8_quantize_sr"
INT8_DEQUANTIZE = "hvd_int8_dequantize"
# ops/linear_attention.py: the recurrence's two kernels, made under the
# scope KDA and named with it as their prefix, so that a reader of a
# trace finds the layer by either (the backward's op_name loses the scope
# under transpose(); the instruction's own name keeps it)
KDA_FWD = KDA + "_fwd"
KDA_BWD = KDA + "_bwd"
# ops/rope.py: the rotation on packed rows and its transpose, made under
# the scope ROPE and named with it as their prefix, as KDA's are
ROPE_FWD = ROPE + "_fwd"
ROPE_BWD = ROPE + "_bwd"
# ops/ssd.py: the state-space scan's two kernels, made under the scope SSD
# and named with it as their prefix, as KDA's are
SSD_FWD = SSD + "_fwd"
SSD_BWD = SSD + "_bwd"
# ops/short_conv.py: ``conv_act``'s two kernels (the convolution with its
# bias and SiLU before a scan), made under the scope SHORT_CONV and named
# with it as their prefix, as KDA's are
SHORT_CONV_FWD = SHORT_CONV + "_fwd"
SHORT_CONV_BWD = SHORT_CONV + "_bwd"

STEP_SCOPES = (REDUCE, REDUCE_PACK, REDUCE_UNPACK, UPDATE, LM_HEAD)
LOOP_SCOPES = (LOOP_EXIT,)   # a looped model's step only
MOE_SCOPES = (MOE_ROUTE, MOE_EXPERTS, MOE_SHARED)   # an expert layer's
LINEAR_ATTN_SCOPES = (KDA, GDN)     # a linear-attention layer's, either
SHORT_CONV_SCOPES = (SHORT_CONV,)   # a gated-convolution layer's
STATE_SPACE_SCOPES = (SSD,)  # a state-space layer's
BLOCK_DIFFUSION_SCOPES = (BD_NOISE,)    # a block-diffusion loss's
# a block's parts: ROPE where positions are rotary, LOSS where the
# cross-entropy is the model's own
BLOCK_SCOPES = (MIXER_PROJ, ROPE, MLP, NORM, EMBED, LOSS)
FLASH_KERNELS = (FLASH_FWD, FLASH_DQ, FLASH_DKV)
SWA_KERNELS = (SWA_FWD, SWA_BWD)
BUCKET_KERNELS = (SCALE, ADASUM_DOT_NORMS, ADASUM_COMBINE, INT8_QUANTIZE,
                  INT8_QUANTIZE_SR, INT8_DEQUANTIZE)
KDA_KERNELS = (KDA_FWD, KDA_BWD)
ROPE_KERNELS = (ROPE_FWD, ROPE_BWD)
SSD_KERNELS = (SSD_FWD, SSD_BWD)
SHORT_CONV_KERNELS = (SHORT_CONV_FWD, SHORT_CONV_BWD)
