"""Offline autotuner for the Pallas kernels (ISSUE 12).

TVM-style search (arXiv:1802.04799), scoped to the kernels this repo
hand-tuned: the flash-attention forward's ``block_q × block_k`` tiles
(``dl/pallas_attention.py`` ships 256/auto), the GBDT histogram's
``feat_block × block_rows`` tiles (``lightgbm/pallas_hist.py`` ships
8/2048), and the paged decode attention's ``block_kv × slots_tile``
(``dl/pallas_paged_attention.py`` ships block_len/1; ISSUE 18). The
tuner

- enumerates a DETERMINISTIC candidate grid respecting the same VMEM
  budget logic the kernels encode (``_resolve_block_k``'s per-block
  byte budget and hard 2048 cap; the histogram's block byte ceiling),
- measures REAL wall clock per config (best-of-``reps`` after a
  warmup/compile pass; the measure fn is injectable so tests feed
  synthetic timings),
- discards anything that fails to compile or times non-finite — a
  broken config can never become a winner
  (``perf_autotune_discarded_total{reason=error|nonfinite}``),
- persists winners keyed by ``(kernel, shape-bucket, platform)`` to a
  JSON registry under :func:`~.costmodel.perf_root` that the kernels
  consult at call time — serving boots with measured-best tiles,
  never search-at-request-time.

Determinism: same candidate grid + same measured timings → the same
winner file, byte for byte (ties break on candidate order, the file is
written sorted).

The in-process winner table (:func:`kernel_winner`) is a PLAIN dict
read — no lock, no IO, no clock — because the kernels consult it at
jit trace time, where any of those is a trace-safety hazard
(graftcheck gates them). :func:`load` populates it (automatically at
import when a registry file exists) and :func:`_search` updates it.

CLI::

    python -m mmlspark_tpu.perf.autotune attention --t 2048 --d 64
    python -m mmlspark_tpu.perf.autotune hist --rows 65536 \
        --features 32 --bins 64
    python -m mmlspark_tpu.perf.autotune paged --context 4096 \
        --block-len 128 --heads 8 --d 64
    python -m mmlspark_tpu.perf.autotune list

Module import is stdlib + numpy + obs/sched only (no JAX); the measure
functions import JAX lazily.
"""

from __future__ import annotations

import json
import logging
import math
import os
import time

from ..obs import registry as _default_registry
from ..sched.policy import bucket_of
from .costmodel import perf_root

_LOG = logging.getLogger("mmlspark_tpu.perf")

__all__ = ["registry_path", "attn_key", "hist_key", "paged_key",
           "kernel_winner", "lookup_stats", "clear", "load",
           "maybe_load", "save", "attention_candidates",
           "hist_candidates", "paged_candidates", "tune_attention",
           "tune_hist", "tune_paged_attention"]

REGISTRY_VERSION = 1

# candidate grids (deterministic order — ties resolve to the earlier
# entry, so the winner file is a pure function of the timings)
_ATTN_BQ = (128, 256, 512)
_ATTN_BK = (256, 512, 1024, 2048)
_HIST_FB = (8, 16)
_HIST_BR = (512, 1024, 2048, 4096)
_PAGED_BKV = (128, 256, 512, 1024, 2048)
_PAGED_ST = (1, 2, 4, 8)

# histogram per-cell VMEM ceiling for candidate filtering: bins block
# (fb × br i32) + vals block (3 × br f32) + output (fb × 3 × bins f32),
# double-buffered headroom left out of a ~16 MiB VMEM
_HIST_VMEM_BYTES = 6 * 1024 * 1024


def registry_path() -> str:
    return os.environ.get("MMLSPARK_TPU_TUNE_STORE") or \
        os.path.join(perf_root(), "autotune.json")


def attn_key(T: int, D: int, causal: bool = False) -> str:
    """Shape bucket for attention: sequence length rounded to its
    power-of-two bucket (one winner serves the whole padded bucket,
    mirroring serving's padding discipline), head dim exact."""
    return f"T{bucket_of(int(T))}-D{int(D)}-c{int(bool(causal))}"


def hist_key(n: int, F: int, num_bins: int) -> str:
    return f"n{bucket_of(int(n))}-F{int(F)}-B{int(num_bins)}"


def paged_key(context: int, D: int, w: int = 1) -> str:
    """Shape bucket for paged decode attention: resident context
    (``max_blocks × block_len``) rounded to its power-of-two bucket —
    one winner serves every table size padding into it — head dim and
    verify-window width exact (w=1 plain decode, w=k+1 speculative)."""
    return f"L{bucket_of(int(context))}-D{int(D)}-w{int(w)}"


# ------------------------------------------------- in-process winner table
_WINNERS: dict[str, dict] = {}
_lookup_hits: dict[str, int] = {}
_lookup_misses: dict[str, int] = {}


def kernel_winner(kernel: str, shape_key: str,
                  platform: str) -> dict | None:
    """The call-time consult: a plain dict read (trace-safe — kernels
    call this while being traced). ``None`` = untuned shape, the kernel
    keeps its default tiles. Hit/miss tallies are lock-free dict bumps
    (GIL-atomic, same discipline as ``CompileTracker``)."""
    w = _WINNERS.get(f"{kernel}|{shape_key}|{platform}")
    if w is not None:
        _lookup_hits[kernel] = _lookup_hits.get(kernel, 0) + 1
    else:
        _lookup_misses[kernel] = _lookup_misses.get(kernel, 0) + 1
    return w


def lookup_stats() -> dict:
    return {"hits": dict(_lookup_hits), "misses": dict(_lookup_misses)}


def clear() -> None:
    """Drop the in-process table (tests)."""
    _WINNERS.clear()
    _lookup_hits.clear()
    _lookup_misses.clear()


def load(path: str | None = None) -> int:
    """Replace the in-process table from a registry file."""
    path = path or registry_path()
    with open(path, encoding="utf-8") as f:
        payload = json.load(f)
    if payload.get("version") != REGISTRY_VERSION:
        raise ValueError(
            f"autotune registry {path!r} has version "
            f"{payload.get('version')}; expected {REGISTRY_VERSION}")
    winners = {str(k): dict(v)
               for k, v in payload.get("winners", {}).items()}
    _WINNERS.clear()
    _WINNERS.update(winners)
    return len(winners)


def maybe_load() -> int:
    """Best-effort boot load: absent registry → 0 winners, never an
    error (runs at module import so serving boots tuned)."""
    try:
        path = registry_path()
        if os.path.exists(path):
            n = load(path)
            _LOG.info("autotune registry loaded %d winners from %s",
                      n, path)
            return n
    except Exception:
        _LOG.warning("autotune registry load failed", exc_info=True)
    return 0


def save(path: str | None = None) -> str:
    """Persist the in-process table (atomic tmp+replace, sorted keys —
    identical winners produce an identical file)."""
    path = path or registry_path()
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    payload = {"version": REGISTRY_VERSION,
               "winners": {k: _WINNERS[k] for k in sorted(_WINNERS)}}
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
    os.replace(tmp, path)
    return path


# ------------------------------------------------------ candidate grids
def _attn_bk_budget(D: int, itemsize: int) -> int:
    """Mirror of ``pallas_attention._resolve_block_k``'s per-block K
    budget (imported from the kernel when JAX is importable, so the two
    can never drift silently; the literal fallback keeps candidate
    enumeration JAX-free)."""
    try:
        from ..dl.pallas_attention import _AUTO_BK_BYTES
        budget = _AUTO_BK_BYTES
    except Exception:
        budget = 512 * 1024
    return budget // max(D * itemsize, 1) // 128 * 128


def attention_candidates(T: int, D: int, *, causal: bool = False,
                         itemsize: int = 4) -> list[dict]:
    """The ``block_q × block_k`` grid for one attention shape,
    respecting the kernel's own VMEM logic: k-blocks are 128-multiples
    within the per-block byte budget and the hard 2048 cap (the fused
    backward's score blocks), and no block exceeds the padded row."""
    tq = max(-(-int(T) // 8) * 8, 8)
    tk = max(-(-int(T) // 128) * 128, 128)
    bk_cap = min(_attn_bk_budget(D, itemsize), 2048)
    seen, out = set(), []
    for bq in _ATTN_BQ:
        bq_eff = min(bq, tq)
        for bk in _ATTN_BK:
            if bk > bk_cap:
                continue
            bk_eff = min(bk, tk)
            cfg = (bq_eff, bk_eff)
            if cfg in seen:
                continue
            seen.add(cfg)
            out.append({"block_q": bq_eff, "block_k": bk_eff})
    return out


def paged_candidates(context: int, block_len: int, heads: int,
                     head_dim: int, *, w: int = 1,
                     itemsize: int = 4) -> list[dict]:
    """The ``block_kv × slots_tile`` grid for one paged-decode shape.
    ``block_kv`` is the score-chunk width inside one pool block — the
    same per-chunk K-byte budget and hard 2048 cap as
    ``_resolve_block_k`` apply, and a chunk never exceeds ``block_len``
    (the kernel streams whole pool blocks; chunking past one is
    meaningless). ``slots_tile`` packs slots per parallel grid row —
    pure launch geometry, results invariant. The kernel's own default
    (whole block, one slot) is always candidate 0, so an untuned-equal
    winner is representable."""
    bl = max(int(block_len), 1)
    bkv_cap = min(_attn_bk_budget(head_dim, itemsize), 2048)
    seen, out = set(), []
    for bkv in (bl,) + _PAGED_BKV:
        if bkv > bkv_cap and bkv != bl:
            continue
        bkv_eff = max(min(bkv, bl), 1)
        for st in _PAGED_ST:
            cfg = (bkv_eff, st)
            if cfg in seen:
                continue
            seen.add(cfg)
            out.append({"block_kv": bkv_eff, "slots_tile": st})
    return out


def hist_candidates(n: int, F: int, num_bins: int) -> list[dict]:
    """The ``feat_block × block_rows`` grid for one histogram shape,
    filtered by the per-cell VMEM ceiling and capped at one row block
    past the data (bigger just pads)."""
    out = []
    for fb in _HIST_FB:
        for br in _HIST_BR:
            if br > 2 * max(int(n), _HIST_BR[0]):
                continue
            cell = (fb * br + 3 * br + fb * 3 * int(num_bins)) * 4
            if cell > _HIST_VMEM_BYTES:
                continue
            out.append({"feat_block": fb, "block_rows": br})
    return out


# ------------------------------------------------------- measurement
def current_platform() -> str:
    try:
        from ..utils.platform import target_platform
        return target_platform()
    except Exception:
        return "cpu"


def _time_best(run, reps: int) -> float:
    """Best-of-``reps`` wall ms after one warmup (compile) pass — the
    same min-of-runs discipline bench.py uses: the minimum is the
    deterministic floor, contention only ever adds."""
    run()  # warmup: compile happens here; a broken config raises here
    best = math.inf
    for _ in range(max(int(reps), 1)):
        t0 = time.perf_counter()
        run()
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def measure_attention(config: dict, *, T: int, D: int,
                      causal: bool = False, batch: int = 1,
                      heads: int = 1, reps: int = 3, seed: int = 0,
                      interpret: bool | None = None) -> float:
    """Real wall-clock ms for one (block_q, block_k) config on
    deterministic inputs (seeded). Raises on compile failure — the
    search discards such configs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..dl.pallas_attention import flash_attention

    rng = np.random.default_rng(seed)
    shape = (batch, heads, T, D)
    q = jnp.asarray(rng.normal(size=shape), jnp.float32)
    k = jnp.asarray(rng.normal(size=shape), jnp.float32)
    v = jnp.asarray(rng.normal(size=shape), jnp.float32)
    mask = jnp.ones((batch, T), bool)

    def run():
        out = flash_attention(
            q, k, v, key_mask=mask, block_q=int(config["block_q"]),
            block_k=int(config["block_k"]), causal=causal,
            interpret=interpret, bwd_impl="blockwise")
        jax.block_until_ready(out)

    return _time_best(run, reps)


def measure_paged_attention(config: dict, *, context: int,
                            block_len: int, heads: int, head_dim: int,
                            w: int = 1, slots: int = 4, reps: int = 3,
                            seed: int = 0,
                            interpret: bool | None = None) -> float:
    """Real wall-clock ms for one (block_kv, slots_tile) config:
    ``slots`` full chains of ``context // block_len`` pool blocks,
    deterministic inputs (seeded). Raises on compile failure — the
    search discards such configs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..dl.pallas_paged_attention import paged_window_attention

    mb = max(int(context) // max(int(block_len), 1), 1)
    nb = slots * mb + 1  # + the TRASH_BLOCK scratch row
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(slots, heads, w, head_dim)),
                    jnp.float32)
    kp = jnp.asarray(rng.normal(
        size=(nb, block_len, heads * head_dim)), jnp.float32)
    vp = jnp.asarray(rng.normal(
        size=(nb, block_len, heads * head_dim)), jnp.float32)
    rows = jnp.asarray(
        1 + np.arange(slots * mb).reshape(slots, mb), jnp.int32)
    pos = jnp.full((slots,), mb * int(block_len) - w, jnp.int32)
    impl = "pallas" if interpret else None

    def run():
        out = paged_window_attention(
            q, kp, vp, rows, pos, block_kv=int(config["block_kv"]),
            slots_tile=int(config["slots_tile"]), impl=impl,
            interpret=interpret)
        jax.block_until_ready(out)

    return _time_best(run, reps)


def measure_hist(config: dict, *, n: int, F: int, num_bins: int,
                 reps: int = 3, seed: int = 0,
                 interpret: bool | None = None) -> float:
    import jax
    import numpy as np

    from ..lightgbm.pallas_hist import hist_pallas, use_pallas_hist

    if interpret is None:
        interpret = not use_pallas_hist()
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, num_bins, size=(n, F)).astype(np.int32)
    vals = rng.normal(size=(n, 3)).astype(np.float32)

    def run():
        out = hist_pallas(
            bins, vals, num_bins=int(num_bins),
            block_rows=int(config["block_rows"]),
            feat_block=int(config["feat_block"]), interpret=interpret)
        jax.block_until_ready(out)

    return _time_best(run, reps)


# ----------------------------------------------------------- the search
def _search(kernel: str, shape_key: str, candidates: list[dict],
            measure, *, platform: str, registry=None,
            persist: bool = True, path: str | None = None) -> dict:
    """Measure every candidate, keep the fastest VALID one, persist it.
    A config that raises (compile failure) or times non-finite/zero is
    discarded and can never be persisted as a winner; ties break on
    candidate order so the registry is a pure function of the
    timings."""
    reg = registry if registry is not None else _default_registry
    c_trials = reg.counter(
        "perf_autotune_trials_total",
        "autotuner configs measured, by kernel")
    c_disc = reg.counter(
        "perf_autotune_discarded_total",
        "autotuner configs discarded, by kernel/reason "
        "(error | nonfinite)")
    c_win = reg.counter(
        "perf_autotune_winners_total",
        "winner entries recorded, by kernel")
    valid: list[tuple[float, int, dict]] = []
    trials = []
    for i, cfg in enumerate(candidates):
        c_trials.inc(1, kernel=kernel)
        try:
            ms = float(measure(cfg))
        except Exception as e:
            _LOG.warning("autotune %s %s: config %s DISCARDED "
                         "(failed: %s)", kernel, shape_key, cfg, e)
            c_disc.inc(1, kernel=kernel, reason="error")
            trials.append({**cfg, "ms": None, "discarded": "error"})
            continue
        if not math.isfinite(ms) or ms <= 0:
            _LOG.warning("autotune %s %s: config %s DISCARDED "
                         "(non-finite timing %r)", kernel, shape_key,
                         cfg, ms)
            c_disc.inc(1, kernel=kernel, reason="nonfinite")
            trials.append({**cfg, "ms": None, "discarded": "nonfinite"})
            continue
        trials.append({**cfg, "ms": round(ms, 4)})
        valid.append((ms, i, cfg))
    record = {"kernel": kernel, "key": shape_key, "platform": platform,
              "trials": trials, "candidates": len(candidates),
              "valid": len(valid), "winner": None}
    if not valid:
        _LOG.warning("autotune %s %s: NO valid config — nothing "
                     "persisted, kernel keeps its defaults",
                     kernel, shape_key)
        return record
    ms, _, cfg = min(valid, key=lambda r: (r[0], r[1]))
    entry = dict(cfg)
    entry["ms"] = round(ms, 4)
    _WINNERS[f"{kernel}|{shape_key}|{platform}"] = entry
    c_win.inc(1, kernel=kernel)
    record["winner"] = entry
    if persist:
        record["path"] = save(path)
    return record


def tune_attention(T: int, D: int, *, causal: bool = False,
                   batch: int = 1, heads: int = 1, reps: int = 3,
                   seed: int = 0, platform: str | None = None,
                   measure=None, interpret: bool | None = None,
                   persist: bool = True, path: str | None = None,
                   registry=None) -> dict:
    platform = platform or current_platform()
    cands = attention_candidates(T, D, causal=causal)
    meas = measure or (lambda cfg: measure_attention(
        cfg, T=T, D=D, causal=causal, batch=batch, heads=heads,
        reps=reps, seed=seed, interpret=interpret))
    return _search("flash_attention", attn_key(T, D, causal), cands,
                   meas, platform=platform, registry=registry,
                   persist=persist, path=path)


def tune_paged_attention(context: int, block_len: int, heads: int,
                         head_dim: int, *, w: int = 1, slots: int = 4,
                         reps: int = 3, seed: int = 0,
                         platform: str | None = None, measure=None,
                         interpret: bool | None = None,
                         persist: bool = True, path: str | None = None,
                         registry=None) -> dict:
    platform = platform or current_platform()
    cands = paged_candidates(context, block_len, heads, head_dim, w=w)
    meas = measure or (lambda cfg: measure_paged_attention(
        cfg, context=context, block_len=block_len, heads=heads,
        head_dim=head_dim, w=w, slots=slots, reps=reps, seed=seed,
        interpret=interpret))
    return _search("paged_attn", paged_key(context, head_dim, w),
                   cands, meas, platform=platform, registry=registry,
                   persist=persist, path=path)


def tune_hist(n: int, F: int, num_bins: int, *, reps: int = 3,
              seed: int = 0, platform: str | None = None,
              measure=None, interpret: bool | None = None,
              persist: bool = True, path: str | None = None,
              registry=None) -> dict:
    platform = platform or current_platform()
    cands = hist_candidates(n, F, num_bins)
    meas = measure or (lambda cfg: measure_hist(
        cfg, n=n, F=F, num_bins=num_bins, reps=reps, seed=seed,
        interpret=interpret))
    return _search("hist", hist_key(n, F, num_bins), cands, meas,
                   platform=platform, registry=registry,
                   persist=persist, path=path)


# ------------------------------------------------------------------- CLI
def _cli(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m mmlspark_tpu.perf.autotune",
        description="Offline Pallas-kernel autotuner: measure tile "
                    "configs, persist winners the kernels load at "
                    "call time")
    sub = ap.add_subparsers(dest="cmd", required=True)
    at = sub.add_parser("attention", help="tune flash-attention tiles")
    at.add_argument("--t", type=int, required=True)
    at.add_argument("--d", type=int, required=True)
    at.add_argument("--causal", action="store_true")
    at.add_argument("--batch", type=int, default=1)
    at.add_argument("--heads", type=int, default=1)
    hi = sub.add_parser("hist", help="tune GBDT-histogram tiles")
    hi.add_argument("--rows", type=int, required=True)
    hi.add_argument("--features", type=int, required=True)
    hi.add_argument("--bins", type=int, required=True)
    pg = sub.add_parser("paged",
                        help="tune paged-decode-attention tiles")
    pg.add_argument("--context", type=int, required=True)
    pg.add_argument("--block-len", type=int, required=True)
    pg.add_argument("--heads", type=int, required=True)
    pg.add_argument("--d", type=int, required=True)
    pg.add_argument("--w", type=int, default=1,
                    help="query window width (1 = plain decode, "
                         "k+1 = speculative verify)")
    pg.add_argument("--slots", type=int, default=4)
    for p in (at, hi, pg):
        p.add_argument("--reps", type=int, default=3)
        p.add_argument("--seed", type=int, default=0)
        p.add_argument("--path", default=None,
                       help="registry file (default: "
                            "$MMLSPARK_TPU_TUNE_STORE or the per-user "
                            "perf root)")
        p.add_argument("--interpret", action="store_true",
                       help="force the Pallas interpreter (off-TPU "
                            "smoke; timings are NOT device-"
                            "representative)")
    ls = sub.add_parser("list", help="print registry winners")
    ls.add_argument("--path", default=None)
    args = ap.parse_args(argv)

    if args.cmd == "list":
        path = args.path or registry_path()
        if os.path.exists(path):
            load(path)
        for key in sorted(_WINNERS):
            print(f"{key}: {json.dumps(_WINNERS[key], sort_keys=True)}")
        print(f"{len(_WINNERS)} winner(s) in {path}")
        return 0

    path = args.path or registry_path()
    if os.path.exists(path):
        load(path)  # accumulate into the existing registry
    interp = True if args.interpret else None
    if args.cmd == "attention":
        rec = tune_attention(args.t, args.d, causal=args.causal,
                             batch=args.batch, heads=args.heads,
                             reps=args.reps, seed=args.seed,
                             interpret=interp, path=path)
    elif args.cmd == "paged":
        rec = tune_paged_attention(args.context, args.block_len,
                                   args.heads, args.d, w=args.w,
                                   slots=args.slots, reps=args.reps,
                                   seed=args.seed, interpret=interp,
                                   path=path)
    else:
        rec = tune_hist(args.rows, args.features, args.bins,
                        reps=args.reps, seed=args.seed,
                        interpret=interp, path=path)
    print(json.dumps({k: v for k, v in rec.items() if k != "trials"},
                     indent=1, sort_keys=True))
    for t in rec["trials"]:
        print(f"  {t}")
    return 0 if rec["winner"] is not None else 1


# boot-time load: a registry built by the offline CLI is live for every
# kernel call in this process without any wiring (module-level, so the
# IO never runs inside a traced region)
maybe_load()


if __name__ == "__main__":  # pragma: no cover
    import sys as _sys
    # `-m` executes this file as __main__ (a second module object);
    # delegate to the canonical import so the CLI and any library code
    # in-process share one winner table (same trick as core.aot).
    from mmlspark_tpu.perf.autotune import _cli as _canonical_cli
    _sys.exit(_canonical_cli())
