"""Work of the prefill attention kernel: causal attention over each
admitted prompt's true length, in every layer. Counted from the true
lengths, not from the length bucket the prompt is padded to, so a kernel
that drops the padding or skips masked blocks reads a higher share."""
from __future__ import annotations

from typing import Iterable, Tuple

MATCH = "custom-call"        # the only Pallas call in an admission
SPAN = "bench.admit"
ELT_BYTES = 2                   # bfloat16 q, k, v and output


def count(cfg, lengths: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) of the prefill attention of prompts of
    ``lengths`` tokens."""
    H, KV, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    flops = bytes_ = 0.0
    for n in lengths:
        pairs = n * (n + 1) / 2.0                    # causal (q, k) pairs
        flops += 4.0 * H * D * pairs                 # q.k and p.v
        bytes_ += n * D * (2 * H + 2 * KV) * ELT_BYTES
    return flops * cfg.n_layers, bytes_ * cfg.n_layers
