"""The step program's stages, by the names a profiler trace shows.

Each stage of the train step runs under one ``jax.named_scope`` of these
names, so every device operation's ``op_name`` metadata says which stage it
belongs to: ``jvp(frcnn.rpn)/...`` forward, ``transpose(jvp(frcnn.rpn))/...``
backward. A scope nested in another (``frcnn.roi_pool`` inside
``frcnn.box_head``, ``frcnn.input`` inside ``frcnn.trunk``) is the later one
in the path, and the later one is the operation's stage. Scopes are metadata:
they change no instruction of the compiled program.

The names are what a trace reduction keys on (``perf/stagecut.py``), so they
are fixed here and never built from configuration. No jax import: the
constants are read by host-side tools too.
"""

INPUT = "frcnn.input"  # device-side jitter / augment / bucket resample / preprocess
TRUNK = "frcnn.trunk"  # extract_features: trunk, FPN neck where there is one
RPN = "frcnn.rpn"  # rpn_forward and the two RPN losses
ANCHOR_TARGETS = "frcnn.anchor_targets"  # batched_anchor_targets
PROPOSALS = "frcnn.proposals"  # propose: decode, clip, top-k, NMS
ROI_TARGETS = "frcnn.roi_targets"  # batched_proposal_targets
ROI_POOL = "frcnn.roi_pool"  # ROIPool / ROIAlign / multilevel align inside the head
BOX_HEAD = "frcnn.box_head"  # the tail, the two heads, select_class_deltas, the head losses
UPDATE = "frcnn.update"  # gradient exchange and rounding, the guard, the optimizer, health norms

# the sequence model's stages (models/lm.py), under the same prefix so that one
# trace reduction reads both programs; `frcnn.update` is a stage of both
LM_EMBED = "frcnn.lm_embed"  # the embedding rows and their multiplier
LM_ATTENTION = "frcnn.lm_attention"  # norm, q/k/v, rotary embedding, the output projection
LM_ATTN_CORE = "frcnn.lm_attn_core"  # the attention function alone, inside lm_attention
LM_LINEAR_ATTENTION = "frcnn.lm_linear_attention"  # a delta-rule layer's norm, projections, convolution, gates, gated norm
LM_DELTA_CORE = "frcnn.lm_delta_core"  # the gated delta rule alone, inside lm_linear_attention
LM_FFN = "frcnn.lm_ffn"  # norm, the dense SwiGLU or the shared expert
LM_ROUTER = "frcnn.lm_router"  # scores, top-k, the sort by expert, counts, the balance bias
LM_EXPERTS = "frcnn.lm_experts"  # rows gathered by expert, the weighted combine by token
LM_EXPERT_MM = "frcnn.lm_expert_mm"  # the grouped products alone, inside lm_experts
LM_HEAD = "frcnn.lm_head"  # last norm, output head, cross-entropy

LM_STAGES = (
    LM_EMBED,
    LM_ATTENTION,
    LM_ATTN_CORE,
    LM_LINEAR_ATTENTION,
    LM_DELTA_CORE,
    LM_FFN,
    LM_ROUTER,
    LM_EXPERTS,
    LM_EXPERT_MM,
    LM_HEAD,
    UPDATE,
)

STAGES = (
    INPUT,
    TRUNK,
    RPN,
    ANCHOR_TARGETS,
    PROPOSALS,
    ROI_TARGETS,
    ROI_POOL,
    BOX_HEAD,
    UPDATE,
)
