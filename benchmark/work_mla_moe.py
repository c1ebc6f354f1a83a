"""Needed work of a decoder of latent-attention blocks with expert layers
that generates through a paged latent cache, from shapes: the operations
and bytes NO implementation can avoid (the companion of ``work.py`` and
``work_decoder.py``, kept with the benchmark for the same reason). The
configuration's keys are DeepSeek-V2's ``config.json`` keys, plus
``experts_held``, ``param_dtype`` and ``cache_dtype``.

A program CALL is a list of sequences ``(doc, doc_len, first, rows)``: the
call computes ``rows`` new rows of a sequence at positions ``first ..
first + rows - 1``, whose first ``doc_len`` positions are the shared
document ``doc`` (None: nothing shared). A row at position ``p`` attends
the ``p + 1`` positions up to itself.

FLOPs are per sequence: every slot's rows multiply with every position
they attend. BYTES count what is distinct: a cached position once a call
however many slots share it, the weights once a call, a held expert that
got a token once a call. So a kernel that reads a shared document once for
sixteen slots cannot read over 100 %.
"""

from __future__ import annotations

from benchmark.work_decoder import ITEMSIZE


def _d(cfg: dict) -> dict:
    lo, hi = cfg.get("experts_held", (0, cfg["n_routed_experts"]))
    return {
        "D": int(cfg["hidden_size"]), "H": int(cfg["num_attention_heads"]),
        "C": int(cfg["kv_lora_rank"]), "R": int(cfg["qk_rope_head_dim"]),
        "nope": int(cfg["qk_nope_head_dim"]), "vh": int(cfg["v_head_dim"]),
        "qr": int(cfg["q_lora_rank"]), "F": int(cfg["intermediate_size"]),
        "Fe": int(cfg["moe_intermediate_size"]),
        "E": int(cfg["n_routed_experts"]), "held": int(hi) - int(lo),
        "shared": int(cfg["n_shared_experts"]),
        "L": int(cfg["num_hidden_layers"]),
        "dense": int(cfg["first_k_dense_replace"]),
        "V": int(cfg["vocab_size"])}


def attention_params(cfg: dict) -> int:
    """One layer's attention matrices (``q_a``, ``q_b``, ``kv_a``,
    ``kv_b``, ``o``): a token multiplies through each once, in the
    published form and in the absorbed one alike (``kv_b``'s two halves
    are what the absorbed form applies to the query and to the output)."""
    d = _d(cfg)
    return (d["D"] * d["qr"] + d["qr"] * d["H"] * (d["nope"] + d["R"])
            + d["D"] * (d["C"] + d["R"])
            + d["C"] * d["H"] * (d["nope"] + d["vh"])
            + d["H"] * d["vh"] * d["D"])


def expert_params(cfg: dict) -> int:
    """One routed expert's three matrices: ``3 * hidden * width``."""
    d = _d(cfg)
    return 3 * d["D"] * d["Fe"]


def token_params(cfg: dict) -> int:
    """The matrices EVERY token multiplies through, all layers: attention,
    the dense layers' MLP, and in each expert layer the router and the
    shared experts. The routed experts are counted by pair."""
    d = _d(cfg)
    moe_layers = d["L"] - d["dense"]
    return (d["L"] * attention_params(cfg)
            + d["dense"] * 3 * d["D"] * d["F"]
            + moe_layers * (d["D"] * d["E"]
                            + 3 * d["D"] * d["shared"] * d["Fe"]))


def head_params(cfg: dict) -> int:
    d = _d(cfg)
    return d["D"] * d["V"]


def position_flops(cfg: dict) -> int:
    """One query row of all heads against one cached position of one
    layer: the scores over ``latent + rope`` numbers and the weighted sum
    over ``latent``: ``2 * heads * (C + R + C)``."""
    d = _d(cfg)
    return 2 * d["H"] * (d["C"] + d["R"] + d["C"])


def position_bytes(cfg: dict) -> int:
    """One cached position of one layer: ``latent + rope`` numbers."""
    d = _d(cfg)
    return (d["C"] + d["R"]) * ITEMSIZE[cfg["cache_dtype"]]


def attended(call: list) -> int:
    """Positions attended, summed over the call's rows."""
    return sum((2 * first + rows + 1) * rows // 2
               for _, _, first, rows in call)


def distinct_positions(call: list) -> int:
    """Cached positions the call reads, each counted once: a shared
    document once however many of the call's sequences hold it, then
    every sequence's own positions past its document."""
    docs = {doc: doc_len for doc, doc_len, _, _ in call if doc is not None}
    own = sum(first + rows - (doc_len if doc is not None else 0)
              for doc, doc_len, first, rows in call)
    return sum(docs.values()) + own


def kernel_work(cfg: dict, call: list) -> dict:
    """The latent attention kernel's needed work in one call, all layers:
    FLOPs of every row against every position it attends; bytes of every
    distinct cached position once, plus each row's query (``C + R`` a
    head) read and output (``C`` a head) written."""
    d = _d(cfg)
    rows = sum(r for _, _, _, r in call)
    item = ITEMSIZE[cfg["cache_dtype"]]
    return {
        "flops": d["L"] * position_flops(cfg) * attended(call),
        "bytes": d["L"] * (position_bytes(cfg) * distinct_positions(call)
                           + rows * d["H"] * (2 * d["C"] + d["R"]) * item)}


def step_work(cfg: dict, call: list, *, held_pairs: int,
              experts_touched: int, logit_rows: int) -> dict:
    """One program call's needed work: every row through the matrices
    every token meets, ``held_pairs`` token-expert pairs through an
    expert each (``6 * hidden * width`` FLOPs a pair), attention as
    :func:`kernel_work` counts it, ``logit_rows`` rows of logits; bytes:
    the weights every call reads once (attention, dense MLP, routers,
    shared experts, the head), each held expert that got a token
    (``experts_touched``, summed over the layers) once, every distinct
    cached position once."""
    d = _d(cfg)
    rows = sum(r for _, _, _, r in call)
    item = ITEMSIZE[cfg["param_dtype"]]
    return {
        "flops": (2 * token_params(cfg) * rows
                  + 2 * expert_params(cfg) * int(held_pairs)
                  + d["L"] * position_flops(cfg) * attended(call)
                  + 2 * head_params(cfg) * int(logit_rows)),
        "bytes": ((token_params(cfg) + head_params(cfg)
                   + expert_params(cfg) * int(experts_touched)) * item
                  + d["L"] * position_bytes(cfg)
                  * distinct_positions(call))}
