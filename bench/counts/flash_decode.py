"""Work of the decode attention kernel: one query token per active slot
against that slot's live K/V, ``length`` positions (the new token
included), in every layer. Counted from the true lengths, never from the
cache's ``max_len``, so a kernel that skips dead blocks reads a higher
share, never one over 100%."""
from __future__ import annotations

from typing import Iterable, Tuple

MATCH = "custom-call"        # the only Pallas call in a decode step
SPAN = "bench.step"
KV_BYTES = 2                    # bfloat16 cache, as the configuration computes


def count(cfg, lengths: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) of one engine step over slots of ``lengths``."""
    H, KV, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    flops = bytes_ = 0.0
    for n in lengths:
        flops += 4.0 * H * D * n                     # q.k and p.v
        bytes_ += 2.0 * n * KV * D * KV_BYTES        # K and V read once
        bytes_ += 2.0 * H * D * KV_BYTES             # q in, out written
    return flops * cfg.n_layers, bytes_ * cfg.n_layers
