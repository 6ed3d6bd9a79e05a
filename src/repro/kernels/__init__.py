"""Pallas TPU kernels for the paper's compute hot-spots (compiled with
Mosaic on the TPU; run by the Pallas interpreter in the CPU test suite):

  chess_hvp    -- the paper's Fig. 2 L2 batched-HVP CUDA kernel, TPU-adapted
  hdual_linear -- fused (2c+2)-component hDual matmul sharing W tiles
"""

from repro.kernels.ops import (chess_hvp, hdual_linear, hdual_linear_apply)

__all__ = ["chess_hvp", "hdual_linear", "hdual_linear_apply"]
