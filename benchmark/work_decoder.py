"""Needed work of a decoder-only transformer that generates through a
key-value cache, from shapes: the operations and bytes the algorithm
cannot avoid, whatever program implements it (the companion of
``work.py``, kept with the benchmark for the same reason). The
configuration's keys are XGLM's: ``d_model``, ``ffn_dim``, ``num_layers``,
``vocab_size``, ``param_dtype``, ``cache_dtype``.
"""

from __future__ import annotations

ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2, "float8_e4m3fn": 1,
            "int8": 1}


def block_matmul_params(cfg: dict) -> int:
    """Parameters of the blocks' matrices (q, k, v, out, fc1, fc2): every
    token multiplies through each once. Biases and LayerNorms are a
    thousandth of that and no MXU work."""
    d, f = int(cfg["d_model"]), int(cfg["ffn_dim"])
    return int(cfg["num_layers"]) * (4 * d * d + 2 * d * f)


def head_flops(cfg: dict) -> int:
    """One row of logits: the final activation times the vocabulary."""
    return 2 * int(cfg["d_model"]) * int(cfg["vocab_size"])


def span_flops(cfg: dict, start: int, stop: int, logit_rows: int) -> int:
    """Multiply-add FLOPs (2 per MAC) of the tokens at positions
    ``start..stop-1`` of one sequence, each attending the ``position +
    1`` keys up to itself (scores and the weighted sum: ``4 * d_model``
    a key a layer), with ``logit_rows`` rows of logits: one a prompt (its
    last position's), one a generated token."""
    n = max(int(stop) - int(start), 0)
    keys = (int(start) + 1 + int(stop)) * n // 2      # sum of (pos + 1)
    return (2 * block_matmul_params(cfg) * n
            + 4 * int(cfg["d_model"]) * int(cfg["num_layers"]) * keys
            + head_flops(cfg) * int(logit_rows))


def kv_bytes_per_token(cfg: dict) -> int:
    """One position's keys and values over all layers."""
    return (2 * int(cfg["num_layers"]) * int(cfg["d_model"])
            * ITEMSIZE[cfg["cache_dtype"]])


def weight_bytes(cfg: dict) -> int:
    """What one program call has to read of the weights: the blocks'
    matrices and the vocabulary matrix once (the embedding's rows are
    that same matrix where the head is tied)."""
    return ((block_matmul_params(cfg)
             + int(cfg["d_model"]) * int(cfg["vocab_size"]))
            * ITEMSIZE[cfg["param_dtype"]])
