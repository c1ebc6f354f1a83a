"""Needed work of a decoder that mixes KDA layers (the gated delta rule with
a decay a key channel) with latent attention layers over one paged latent
pool, a dense MLP in its first layers and an expert layer with a shared
expert in the others, from shapes and the requests' lengths: the operations
and bytes NO implementation of these equations can avoid (the companion of
``work_gated_delta_moe.py`` and ``work_mla_moe.py``, kept with the
benchmark for the same reason). The configuration's keys are
Kimi-Linear's ``config.json`` keys, plus ``experts_held``, ``param_dtype``
and ``cache_dtype``.

ONE PROGRAM a boundary: its decode rows and the prefill window that rides
with them are one list of sequences ``(doc, doc_len, first, rows)`` as
``work_mla_moe`` takes them, and it reads every weight it needs ONCE.

FLOPs are per row: through the matrices every token meets, each held
token-expert pair through its expert, every row against every position it
attends in an MLA layer (``work_mla_moe.position_flops``), and in a KDA
layer the recurrence a row a head (decay the state's rows, read it at
``k``, write ``k d^T``, read it at ``q``: ``7 dk dv``; the chunked form of
a window does more than that and is credited with this). BYTES count what
is distinct: the weights once a program, a held expert that got a token
once a program, every distinct cached position once a program (``latent +
rope`` numbers), every sequence's state once in and once out a KDA layer —
a decode row and a window alike — and its tail likewise, and each row's
``q``, ``k``, ``v``, decay and output. So no share of a peak can read over
100 %.
"""

from __future__ import annotations

from benchmark.work_decoder import ITEMSIZE
from benchmark.work_mla_moe import attended, distinct_positions

KDA, MLA = "kda", "mla"


def _d(cfg: dict) -> dict:
    lin = cfg["linear_attn_config"]
    lo, hi = cfg.get("experts_held") or (0, cfg["num_experts"])
    L = int(cfg["num_hidden_layers"])
    n_mla = sum(1 for i in lin["full_attn_layers"] if int(i) <= L)
    return {
        "D": int(cfg["hidden_size"]), "V": int(cfg["vocab_size"]),
        "H": int(lin["num_heads"]), "dk": int(lin["head_dim"]),
        "taps": int(lin["short_conv_kernel_size"]),
        "Hm": int(cfg["num_attention_heads"]),
        "C": int(cfg["kv_lora_rank"]), "R": int(cfg["qk_rope_head_dim"]),
        "nope": int(cfg["qk_nope_head_dim"]), "vh": int(cfg["v_head_dim"]),
        "I": int(cfg["intermediate_size"]),
        "F": int(cfg["moe_intermediate_size"]),
        "shared": int(cfg["num_shared_experts"]),
        "E": int(cfg["num_experts"]), "held": int(hi) - int(lo),
        "dense": int(cfg["first_k_dense_replace"]), "L": L,
        "n_mla": n_mla, "n_kda": L - n_mla}


def mixer_params(cfg: dict, kind: str) -> int:
    """One mixer's matrices; every token multiplies through each once
    (the absorbed latent form applies ``kv_b``'s halves to the query and
    the output, once a token all the same)."""
    d = _d(cfg)
    D = d["D"]
    if kind == MLA:
        return (D * d["Hm"] * (d["nope"] + d["R"]) + D * (d["C"] + d["R"])
                + d["C"] * d["Hm"] * (d["nope"] + d["vh"])
                + d["Hm"] * d["vh"] * D)
    kd = d["H"] * d["dk"]
    return (3 * D * kd + D * d["H"] + 2 * (D * d["dk"] + d["dk"] * kd)
            + kd * D)


def expert_params(cfg: dict) -> int:
    """One routed expert's three matrices."""
    d = _d(cfg)
    return 3 * d["D"] * d["F"]


def token_params(cfg: dict) -> int:
    """The matrices EVERY token multiplies through, all layers: the
    mixers, the dense layers' MLP, and in every expert layer the router
    and the shared expert. The routed experts are counted by pair."""
    d = _d(cfg)
    return (d["n_mla"] * mixer_params(cfg, MLA)
            + d["n_kda"] * mixer_params(cfg, KDA)
            + d["dense"] * 3 * d["D"] * d["I"]
            + (d["L"] - d["dense"]) * (d["D"] * d["E"]
                                       + 3 * d["D"] * d["shared"] * d["F"]))


def head_params(cfg: dict) -> int:
    d = _d(cfg)
    return d["D"] * d["V"]


def state_bytes(cfg: dict) -> int:
    """One sequence's state in ONE KDA layer (float32)."""
    d = _d(cfg)
    return d["H"] * d["dk"] * d["dk"] * 4


def tail_bytes(cfg: dict) -> int:
    """One sequence's convolution tail in ONE KDA layer."""
    d = _d(cfg)
    return (d["taps"] - 1) * 3 * d["H"] * d["dk"] \
        * ITEMSIZE[cfg["cache_dtype"]]


def position_flops(cfg: dict) -> int:
    """One query row of all heads against one cached position of one MLA
    layer: the scores over ``latent + rope`` numbers and the weighted sum
    over ``latent``."""
    d = _d(cfg)
    return 2 * d["Hm"] * (d["C"] + d["R"] + d["C"])


def position_bytes(cfg: dict) -> int:
    """One cached position of one MLA layer: ``latent + rope`` numbers."""
    d = _d(cfg)
    return (d["C"] + d["R"]) * ITEMSIZE[cfg["cache_dtype"]]


def kernel_work(cfg: dict, call: list) -> dict:
    """Each kernel's needed work in one program, all its layers:
    ``kda_step`` (the sequences of the call that bring ONE row),
    ``kda_chunk`` (those that bring a window), ``latent_attn`` (every
    row's latent attention), each ``{"flops", "bytes"}``."""
    d = _d(cfg)
    item = ITEMSIZE[cfg["cache_dtype"]]
    H, dk = d["H"], d["dk"]
    rule = 7 * dk * dk * H                             # a row, a layer
    # q, k, v in, o out (serving type); the decay a channel and the
    # write strength a head (float32)
    row_io = 4 * dk * H * item + (dk + 1) * H * 4
    out = {}
    for key, part in (("kda_step", [c for c in call if c[3] == 1]),
                      ("kda_chunk", [c for c in call if c[3] > 1])):
        rows = sum(r for _, _, _, r in part)
        out[key] = {
            "flops": d["n_kda"] * rule * rows,
            "bytes": d["n_kda"] * (len(part) * 2 * state_bytes(cfg)
                                   + rows * row_io)}
    rows = sum(r for _, _, _, r in call)
    out["latent_attn"] = {
        "flops": d["n_mla"] * position_flops(cfg) * attended(call),
        "bytes": d["n_mla"] * (
            position_bytes(cfg) * distinct_positions(call)
            + rows * d["Hm"] * (2 * d["C"] + d["R"]) * item)}
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
    conv = 2 * d["taps"] * 3 * d["H"] * d["dk"]
    head = head_params(cfg) if logit_rows else 0
    return {
        "flops": (2 * token_params(cfg) * rows
                  + 2 * expert_params(cfg) * int(held_pairs)
                  + d["n_kda"] * conv * rows
                  + 2 * head_params(cfg) * int(logit_rows)
                  + sum(k["flops"] for k in kernels.values())),
        "bytes": ((token_params(cfg) + head
                   + expert_params(cfg) * int(experts_touched)) * item
                  + d["n_kda"] * len(call) * 2 * tail_bytes(cfg)
                  + sum(k["bytes"] for k in kernels.values())),
        "kernels": kernels}
