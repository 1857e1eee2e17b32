"""Model FLOPs of a dense decoder (GQA attention, GLU MLP): the work the
model needs, with no recomputation, padding or idle slot counted."""
from __future__ import annotations

from typing import Iterable


def layer_matmul_params(cfg) -> float:
    d, D = cfg.d_model, cfg.head_dim
    attn = d * D * (cfg.n_heads + 2 * cfg.n_kv_heads) + cfg.n_heads * D * d
    return float(attn + 3 * d * cfg.d_ff)


def head_params(cfg) -> float:
    return float(cfg.d_model * cfg.vocab)


def decode_flops(cfg, lengths: Iterable[int]) -> float:
    """One decode token per active slot, attending ``length`` positions."""
    per_tok = 2.0 * (cfg.n_layers * layer_matmul_params(cfg)
                     + head_params(cfg))
    attn = 4.0 * cfg.n_heads * cfg.head_dim * cfg.n_layers
    return sum(per_tok + attn * n for n in lengths)


def prefill_flops(cfg, lengths: Iterable[int]) -> float:
    """Prefill of prompts of ``lengths`` tokens, causal attention, and the
    head at the last position only (the first token)."""
    mm = 2.0 * cfg.n_layers * layer_matmul_params(cfg)
    attn = 4.0 * cfg.n_heads * cfg.head_dim * cfg.n_layers
    return sum(mm * n + attn * n * (n + 1) / 2.0 + 2.0 * head_params(cfg)
               for n in lengths)
