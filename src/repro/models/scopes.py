"""Names of the model's layers in a device trace.

The decode and prefill paths wrap each part of a layer in
``jax.named_scope`` with one of these names. The name becomes part of the
HLO ``op_name`` of every operation traced inside it, which the profiler
keeps as the op's ``tf_op``; a trace reduction then puts each device op
down to the innermost (last) of these names on its path. Scopes exist
only while tracing and cost nothing at run time.
"""

QKV = "qkv"  # q/k/v projections, bias, rope
KV_WRITE = "kv_write"  # the new K/V rows written to the cache or the paged pool
# attention of a paged pool (decode and verify) or of a single-token step:
# page gather, scores, softmax and PV, or the flash-decode kernel
DECODE_ATTENTION = "decode_attention"
# attention of a multi-token block against a dense cache (admission prefill,
# suffix prefill)
PREFILL_ATTENTION = "prefill_attention"
ATTN_OUT = "attn_out"  # the output projection
MLP = "mlp"  # pre-FFN norm and the FFN (dense, MoE or rwkv channel mix)
HEAD_SAMPLE = "head_sample"  # final norm, LM head, argmax/categorical, logprob
# the scan over layers around the scopes above: each layer's weights and
# cache sliced out of their stacks, the updated cache written back
LAYER_SCAN = "layer_scan"

SCOPES = (QKV, KV_WRITE, DECODE_ATTENTION, PREFILL_ATTENTION, ATTN_OUT, MLP,
          HEAD_SAMPLE, LAYER_SCAN)
