"""Needed work of a decoder that mixes block-sparse grouped-query attention
layers with lightning (linear) attention layers and generates through a
paged key/value cache plus a state a sequence, from shapes and the
requests' lengths: the operations and bytes NO implementation of these
equations can avoid (the companion of ``work.py``, ``work_decoder.py`` and
``work_mla_moe.py``, kept with the benchmark for the same reason). The
configuration's keys are MiniCPM-SALA's ``config.json`` keys, plus
``sparse_config``, ``param_dtype`` and ``cache_dtype``.

A program CALL is a list of sequences ``(doc, doc_len, first, rows)`` as
``work_mla_moe`` takes them: the call computes ``rows`` new rows of a
sequence at positions ``first .. first + rows - 1``, whose first
``doc_len`` positions are the shared document ``doc``.

What a row at position ``p`` of a sparse layer attends: all ``p + 1``
positions while ``p + 1 <= dense_len``; past that the tokens up to itself
of ``min(topk, blocks)`` blocks of ``block_size`` (the query's own block is
among them, seen as far as the query). FLOPs are per row. BYTES count what
a call NEEDS: the chosen blocks once a (row, key head) but never more than
the distinct cached positions the call can reach (slots that share a
document, and neighbouring rows of a window, choose overlapping blocks);
the compressed keys of every distinct cached position once; a lightning
layer's state once in and once out a sequence. So no kernel can read over
100 %.
"""

from __future__ import annotations

from benchmark.work_decoder import ITEMSIZE
from benchmark.work_mla_moe import distinct_positions

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


def _d(cfg: dict) -> dict:
    sp = cfg["sparse_config"]
    mixers = list(cfg["mixer_types"])
    return {
        "D": int(cfg["hidden_size"]), "F": int(cfg["intermediate_size"]),
        "H": int(cfg["num_attention_heads"]),
        "G": int(cfg["num_key_value_heads"]), "hd": int(cfg["head_dim"]),
        "LH": int(cfg["lightning_nh"]), "lhd": int(cfg["lightning_head_dim"]),
        "V": int(cfg["vocab_size"]),
        "n_sparse": mixers.count(SPARSE), "n_light": mixers.count(LIGHTNING),
        "st": int(sp["kernel_stride"]), "bs": int(sp["block_size"]),
        "topk": int(sp["topk"]), "dense_len": int(sp["dense_len"])}


def layer_params(cfg: dict, mixer: str) -> int:
    """One layer's matrices: the mixer's (q, k, v, gate, o) and the MLP's
    three; every token multiplies through each once."""
    d = _d(cfg)
    mlp = 3 * d["D"] * d["F"]
    if mixer == SPARSE:
        hq, hk = d["H"] * d["hd"], d["G"] * d["hd"]
        return 3 * d["D"] * hq + 2 * d["D"] * hk + mlp
    return 5 * d["D"] * d["LH"] * d["lhd"] + mlp


def token_params(cfg: dict) -> int:
    d = _d(cfg)
    return d["n_sparse"] * layer_params(cfg, SPARSE) \
        + d["n_light"] * layer_params(cfg, LIGHTNING)


def head_params(cfg: dict) -> int:
    d = _d(cfg)
    return d["D"] * d["V"]


def attended(cfg: dict, first: int, rows: int) -> int:
    """Tokens attended in ONE sparse layer, summed over the rows at
    positions ``first .. first + rows - 1``."""
    d = _d(cfg)
    total = 0
    for p in range(first, first + rows):
        if p + 1 <= d["dense_len"]:
            total += p + 1
        else:
            blocks = min(d["topk"], p // d["bs"] + 1)
            total += (blocks - 1) * d["bs"] + p % d["bs"] + 1
    return total


def compressed_keys(cfg: dict, first: int, rows: int) -> int:
    """Compressed keys scored in ONE sparse layer, summed over the rows
    past ``dense_len`` (a key counts once its ``2 * stride`` tokens lie at
    or before the row)."""
    d = _d(cfg)
    return sum(max((p + 1) // d["st"] - 1, 0)
               for p in range(first, first + rows)
               if p + 1 > d["dense_len"])


def kernel_work(cfg: dict, call: list) -> dict:
    """Each kernel's needed work in one call, all its layers:
    ``sparse_attn`` (the block-sparse attention), ``sparse_select`` (the
    scoring pass over the compressed keys) and ``lightning`` (the step or
    its chunked form), each ``{"flops", "bytes"}``."""
    d = _d(cfg)
    item = ITEMSIZE[cfg["cache_dtype"]]
    rows = sum(r for _, _, _, r in call)
    att = sum(attended(cfg, first, r) for _, _, first, r in call)
    reach = distinct_positions(call)
    kv_token = 2 * d["hd"] * item                     # k and v, a key head
    sparse_bytes = d["G"] * kv_token * min(att, reach) \
        + rows * 2 * d["H"] * d["hd"] * item          # q in, o out
    keys = sum(compressed_keys(cfg, first, r) for _, _, first, r in call)
    select_bytes = d["G"] * d["hd"] * item * min(keys, reach // d["st"]) \
        + rows * d["H"] * d["hd"] * item
    state = d["LH"] * d["lhd"] * d["lhd"] * 4
    light_bytes = len(call) * 2 * state \
        + rows * 4 * d["LH"] * d["lhd"] * item        # q, k, v in, o out
    return {
        "sparse_attn": {
            "flops": d["n_sparse"] * 4 * d["H"] * d["hd"] * att,
            "bytes": d["n_sparse"] * sparse_bytes},
        "sparse_select": {
            "flops": d["n_sparse"] * 2 * d["H"] * d["hd"] * keys,
            "bytes": d["n_sparse"] * select_bytes},
        "lightning": {
            "flops": d["n_light"] * 4 * d["LH"] * d["lhd"] * d["lhd"] * rows,
            "bytes": d["n_light"] * light_bytes}}


def step_work(cfg: dict, call: list, *, logit_rows: int) -> dict:
    """One program call's needed work: every row through the matrices,
    the three kernels as :func:`kernel_work` counts them, ``logit_rows``
    rows of logits; bytes: the weights once (the head's with them when a
    row of logits is asked for), the kernels' bytes."""
    item = ITEMSIZE[cfg["param_dtype"]]
    rows = sum(r for _, _, _, r in call)
    kernels = kernel_work(cfg, call)
    head = head_params(cfg) if logit_rows else 0
    return {
        "flops": (2 * token_params(cfg) * rows
                  + 2 * head_params(cfg) * int(logit_rows)
                  + sum(k["flops"] for k in kernels.values())),
        "bytes": ((token_params(cfg) + head) * item
                  + sum(k["bytes"] for k in kernels.values())),
        "kernels": kernels}
