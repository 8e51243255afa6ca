"""What the two kinds of cell share."""

from __future__ import annotations

import sys


class CellFailure(RuntimeError):
    pass


def model_config(cfg: dict, max_seq_len: int, param_dtype: str) -> dict:
    """The published keys under the names ``TransformerConfig`` uses."""
    return dict(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"],
        rope_theta=cfg["rope_theta"],
        norm_eps=cfg["rms_norm_eps"],
        sliding_window=cfg["sliding_window"],
        tie_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["torch_dtype"],
        param_dtype=param_dtype,
        max_seq_len=max_seq_len,
    )


def log(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)
