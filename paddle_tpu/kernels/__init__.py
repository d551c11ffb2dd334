"""Pallas TPU kernels for hot ops.

Implemented here (each with interpret-mode CPU tests):
- flash_attention: forward + backward kernels, causal/non-causal, key-padding
  bias, in-kernel PRNG attention dropout (kernels/flash_attention.py);
- fused layer norm / rms norm forward kernels with closed-form backward
  (kernels/fused_norm.py);
- the chunk-wise gated delta rule of Kimi Delta Attention: a forward and a
  backward kernel that carry the state across a head's chunks in VMEM
  (kernels/delta_rule.py);
- the short convolution in front of it: causal depthwise taps inside
  documents, an optional bias, SiLU and the per-head l2norm in one pass,
  forward and backward (kernels/short_conv.py);
- Mamba-2's state-space rule chunk-wise: a forward and a backward kernel
  that carry the stacked states of a group's heads across its chunks in VMEM
  (kernels/ssd.py);
- the routed experts' grouped matrix product over a row buffer in tiles, one
  expert a tile: a forward kernel (also the input gradient's, on the
  transposed matrix) and the weight gradient's kernel, both skipping the
  tiles that hold no rows (kernels/grouped_matmul.py);
- the routed experts' rows between token order and that buffer: a gather and
  its transpose, a sum by token, which keep the token side in VMEM a column
  chunk at a time and move one row for each row held
  (kernels/row_permute.py);
- the rotary position encoding of q and k in one pass: bfloat16 read once in
  the projections' layout, turned in float32 in VMEM, written once in the
  flash kernels' layout; the backward the same kernel with the sine negated
  (kernels/rotary.py).

These replace the reference's hand-written CUDA/cuDNN kernels
(paddle/fluid/operators/fused/*attention*, layer_norm_op.cu) with TPU-native
Pallas implementations.
"""
from .flash_attention import flash_attention_bhld  # noqa: F401
from .fused_norm import fused_layer_norm, fused_rms_norm  # noqa: F401
