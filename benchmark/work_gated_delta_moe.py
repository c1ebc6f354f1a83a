"""Needed work of a decoder that mixes gated DeltaNet layers with gated
grouped-query attention layers, an expert layer after every mixer, and
generates through a paged key/value cache plus a state and a convolution
tail a sequence, from shapes and the requests' lengths: the operations and
bytes NO implementation of these equations can avoid (the companion of
``work.py``, ``work_decoder.py``, ``work_mla_moe.py`` and
``work_sparse_linear.py``, kept with the benchmark for the same reason).
The configuration's keys are Qwen3-Next's ``config.json`` keys, plus
``layer_types``, ``experts_held``, ``param_dtype`` and ``cache_dtype``.

ONE PROGRAM a boundary (``serving/llm.py`` from PR 34): its decode rows
and the prefill window that rides with them are one list of sequences
``(doc, doc_len, first, rows)`` as ``work_mla_moe`` takes them — the
program computes ``rows`` new rows of a sequence at positions ``first ..
first + rows - 1``, whose first ``doc_len`` positions are the shared
system prompt ``doc`` — and it reads every weight it needs ONCE.

FLOPs are per row: through the matrices every token meets, each held
token-expert pair through its expert, every row against every position it
attends in an attention layer, and in a DeltaNet layer the recurrence a
row a value head (decay the state, read it at ``k``, write ``k d^T``, read
it at ``q``: ``7 dk dv``; the chunked form of a window does more than that
and is credited with this). BYTES count what is distinct: the weights once
a program, a held expert that got a token once a program, every distinct
cached position once a program (the shared prompt once however many slots
chain it), every sequence's state once in and once out a DeltaNet layer —
a decode row and a window alike — and its tail likewise. So no share of a
peak can read over 100 %.
"""

from __future__ import annotations

from benchmark.work_decoder import ITEMSIZE
from benchmark.work_mla_moe import attended, distinct_positions

FULL, LINEAR = "full_attention", "linear_attention"


def _d(cfg: dict) -> dict:
    lo, hi = cfg.get("experts_held") or (0, cfg["num_experts"])
    types = list(cfg["layer_types"])
    return {
        "D": int(cfg["hidden_size"]), "V": int(cfg["vocab_size"]),
        "H": int(cfg["num_attention_heads"]),
        "G": int(cfg["num_key_value_heads"]), "hd": int(cfg["head_dim"]),
        "Hk": int(cfg["linear_num_key_heads"]),
        "Hv": int(cfg["linear_num_value_heads"]),
        "dk": int(cfg["linear_key_head_dim"]),
        "dv": int(cfg["linear_value_head_dim"]),
        "taps": int(cfg["linear_conv_kernel_dim"]),
        "E": int(cfg["num_experts"]), "held": int(hi) - int(lo),
        "F": int(cfg["moe_intermediate_size"]),
        "Fs": int(cfg["shared_expert_intermediate_size"]),
        "L": len(types), "n_full": types.count(FULL),
        "n_linear": types.count(LINEAR)}


def mixer_params(cfg: dict, kind: str) -> int:
    """One mixer's matrices; every token multiplies through each once."""
    d = _d(cfg)
    if kind == FULL:
        return (d["D"] * d["H"] * 2 * d["hd"] + 2 * d["D"] * d["G"] * d["hd"]
                + d["H"] * d["hd"] * d["D"])
    kd, vd = d["Hk"] * d["dk"], d["Hv"] * d["dv"]
    return d["D"] * (2 * kd + 2 * vd) + d["D"] * 2 * d["Hv"] + vd * d["D"]


def expert_params(cfg: dict) -> int:
    """One routed expert's three matrices."""
    d = _d(cfg)
    return 3 * d["D"] * d["F"]


def token_params(cfg: dict) -> int:
    """The matrices EVERY token multiplies through, all layers: the
    mixers, and in every layer the router, the shared expert and its
    gate. The routed experts are counted by pair."""
    d = _d(cfg)
    return (d["n_full"] * mixer_params(cfg, FULL)
            + d["n_linear"] * mixer_params(cfg, LINEAR)
            + d["L"] * (d["D"] * d["E"] + 3 * d["D"] * d["Fs"] + d["D"]))


def head_params(cfg: dict) -> int:
    d = _d(cfg)
    return d["D"] * d["V"]


def state_bytes(cfg: dict) -> int:
    """One sequence's state in ONE DeltaNet layer (float32)."""
    d = _d(cfg)
    return d["Hv"] * d["dk"] * d["dv"] * 4


def tail_bytes(cfg: dict) -> int:
    """One sequence's convolution tail in ONE DeltaNet layer."""
    d = _d(cfg)
    return (d["taps"] - 1) * (2 * d["Hk"] * d["dk"] + d["Hv"] * d["dv"]) \
        * ITEMSIZE[cfg["cache_dtype"]]


def kernel_work(cfg: dict, call: list) -> dict:
    """Each kernel's needed work in one program, all its layers:
    ``gdn_step`` (the sequences of the call that bring ONE row),
    ``gdn_chunk`` (those that bring a window) and ``gqa_attn`` (every
    row's attention), each ``{"flops", "bytes"}``."""
    d = _d(cfg)
    item = ITEMSIZE[cfg["cache_dtype"]]
    rule = 7 * d["dk"] * d["dv"] * d["Hv"]            # a row, a layer
    row_io = (2 * d["dk"] + 2 * d["dv"]) * d["Hv"] * item   # q, k, v, o
    out = {}
    for key, part in (("gdn_step", [c for c in call if c[3] == 1]),
                      ("gdn_chunk", [c for c in call if c[3] > 1])):
        rows = sum(r for _, _, _, r in part)
        out[key] = {
            "flops": d["n_linear"] * rule * rows,
            "bytes": d["n_linear"] * (len(part) * 2 * state_bytes(cfg)
                                      + rows * row_io)}
    rows = sum(r for _, _, _, r in call)
    out["gqa_attn"] = {
        "flops": d["n_full"] * 4 * d["H"] * d["hd"] * attended(call),
        "bytes": d["n_full"] * (
            2 * d["G"] * d["hd"] * item * distinct_positions(call)
            + rows * 2 * d["H"] * d["hd"] * item)}
    return out


def step_work(cfg: dict, call: list, *, held_pairs: int,
              experts_touched: int, logit_rows: int) -> dict:
    """One program's needed work (see the module docstring);
    ``held_pairs`` token-expert pairs through an expert each,
    ``experts_touched`` held experts that got a token (summed over the
    layers), ``logit_rows`` rows of logits."""
    d = _d(cfg)
    item = ITEMSIZE[cfg["param_dtype"]]
    rows = sum(r for _, _, _, r in call)
    kernels = kernel_work(cfg, call)
    conv = 2 * d["taps"] * (2 * d["Hk"] * d["dk"] + d["Hv"] * d["dv"])
    head = head_params(cfg) if logit_rows else 0
    return {
        "flops": (2 * token_params(cfg) * rows
                  + 2 * expert_params(cfg) * int(held_pairs)
                  + d["n_linear"] * conv * rows
                  + 2 * head_params(cfg) * int(logit_rows)
                  + sum(k["flops"] for k in kernels.values())),
        "bytes": ((token_params(cfg) + head
                   + expert_params(cfg) * int(experts_touched)) * item
                  + d["n_linear"] * len(call) * 2 * tail_bytes(cfg)
                  + sum(k["bytes"] for k in kernels.values())),
        "kernels": kernels}
