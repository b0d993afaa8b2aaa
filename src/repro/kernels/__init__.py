"""Pallas TPU kernels for the framework's compute hot-spots.

  costmodel_eval -- batched (design-point x layer) cost evaluation (the
                    search inner loop; DESIGN.md S3)
  lstm_cell      -- fused REINFORCE policy step
  flash_decode   -- online-softmax single-token GQA attention for long-KV
                    serving shapes

``ops`` exposes shape-flexible wrappers that run the kernels on a TPU and
the pure-jnp oracles of ``ref`` elsewhere (``ops.on_tpu``).
"""
from repro.kernels.ops import batched_cost, decode_attention, lstm_step

__all__ = ["batched_cost", "decode_attention", "lstm_step"]
