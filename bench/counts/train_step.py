"""Model FLOPs of one training step of a dense decoder with image
positions: the forward pass and the two matmuls of its backward for each
forward one, over the true sequence, causal attention over the positions
before each, the image features' projection at the image positions, and
the head at the positions whose next token counts in the loss.
Recomputation (remat) and masked positions do not count."""
from __future__ import annotations

from bench.harness.readers import load_count


def step_flops(cfg, batch: int, seq: int) -> float:
    dd = load_count("dense_decoder")
    n_img = int(cfg.frontend_tokens)
    fwd = (2.0 * cfg.n_layers * dd.layer_matmul_params(cfg) * seq
           + 2.0 * cfg.n_layers * cfg.n_heads * cfg.head_dim * seq * (seq + 1)
           + 2.0 * cfg.frontend_dim * cfg.d_model * n_img
           + 2.0 * dd.head_params(cfg) * (seq - max(0, n_img - 1) - 1))
    return 3.0 * batch * fwd
