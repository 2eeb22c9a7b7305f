"""Model zoo: composable JAX definitions for the assigned architectures."""
from repro.models.model import BlockSpec, Model, Segment, derive_segments

# The ``jax.named_scope`` names of the train step.  Each is a segment of
# the ``op_name`` of every device op it wraps, forward, backward and
# recomputed alike, so a profiler trace can be split by them:
#   attention / ssm / mlp / moe   one block's mixer or FFN, with its norm
#                                 and residual add
#   attention_core / ssm_core     softmax(QK^T)V, whatever runs it / the SSD
#   embed, head, loss             embedding; final norm and LM head;
#                                 cross-entropy
#   optimizer                     the AdamW update (and gradient compression)
#   grad_sync                     bucketed sync's per-layer gradient reduce
SCOPES = ("embed", "attention", "attention_core", "ssm", "ssm_core", "mlp",
          "moe", "head", "loss", "optimizer", "grad_sync")

__all__ = ["Model", "BlockSpec", "Segment", "derive_segments", "SCOPES"]
