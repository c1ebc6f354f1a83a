"""Ahead-of-time executable store: compilation as a build step.

Why: every (route, padding-bucket, mesh) combination pays its XLA
compile the first time traffic hits it, so an autoscaler scale-up is a
compile storm on the fresh worker — first-request latency is seconds
against a steady state of milliseconds. Per the full-program
compilation thesis (arXiv:1810.09868) and fingerprint-keyed caching
(arXiv:2008.01040), the fix is to move compilation to build time:
``core/compile.py``'s :class:`~.compile.FusedSegment` is already the
unit of compilation — this module lowers, compiles, serializes, and
reloads it instead of re-tracing per process.

The store is a content-addressed directory tree::

    <root>/<ff[:2]>/<ff>/        ff = full fingerprint (sha256 hex)
        meta.json                key components, specs, tier, checksum
        exe.bin                  serialized executable (tier "serialized")
        hlo.txt                  StableHLO text (debug + retrace tier)

Two fingerprints per entry:

- **static fingerprint** — stage classes + params (fitted state lives
  in params), donation split, host-column contract, mesh descriptor,
  backend platform, jax/jaxlib versions. Everything that decides WHAT
  program a segment lowers to, minus the input shapes.
- **full fingerprint** — static + the column spec (names, dtypes,
  shapes): one entry per padding bucket.

A param change moves the static fingerprint, so stale entries can never
be served (they simply stop matching); :meth:`AotStore.gc` reclaims
them. A corrupt or undeserializable entry is a LOUD miss
(``aot_store_miss_total{reason=...}`` + warning) followed by
compile-and-backfill — never a wrong answer (mirrors
``resilience_checkpoint_skipped_total`` semantics).

Fingerprint computation and store bookkeeping are JAX-free (the CI
smoke asserts it): versions come from ``importlib.metadata``, hashes
from hashlib. Only executable (de)serialization and the build CLI
touch a backend, through :mod:`mmlspark_tpu.parallel.compat`'s
serialize/deserialize split.

Build CLI (see ``docs/aot.md``)::

    python -m mmlspark_tpu.core.aot build --import myapp.serving \\
        --root /var/mmlspark_tpu/aot
    python -m mmlspark_tpu.core.aot list|gc|selftest|verify ...

Warm loading: ``serving/dsl.ServingStream.start`` and
``serving/distributed.remote_worker_loop`` call :func:`maybe_warm`, so
an autoscaler-added worker boots with every registered segment × bucket
already executable — its first request is as fast as its thousandth.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import shutil
import tempfile
import threading

import numpy as np

_LOG = logging.getLogger("mmlspark_tpu.core.aot")

#: default on-disk root (override with MMLSPARK_TPU_AOT_STORE).
#: Per-user: executables deserialize through pickle, so a shared /tmp
#: path would let any local user plant code another user's server
#: boot would execute (maybe_warm additionally refuses roots this uid
#: does not own).
DEFAULT_STORE_ROOT = os.path.join(
    tempfile.gettempdir(), "mmlspark_tpu_aot_store-" + str(
        getattr(os, "getuid", lambda: "u")()))
_META = "meta.json"
_EXE = "exe.bin"
_HLO = "hlo.txt"
STORE_VERSION = 1


def store_root() -> str:
    """The configured store root: ``MMLSPARK_TPU_AOT_STORE`` or the
    default."""
    return os.environ.get("MMLSPARK_TPU_AOT_STORE") or DEFAULT_STORE_ROOT


#: the checkout that holds this package: the persistent compile cache
#: lives under it at a FIXED path (the path is part of the cache key —
#: a directory that moves with a pid, the time or mkdtemp never hits)
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def jax_cache_dir(subdir: str = "") -> str:
    """Where the JAX persistent compilation cache lives. Two rules:
    ``JAX_COMPILATION_CACHE_DIR``, when set, is the whole answer (the
    operator placed the cache from outside); otherwise a fixed
    directory inside the checkout, ``<checkout>/.jax_cache[/subdir]``
    (git-ignored). ``subdir`` keeps the test suite's CPU executables
    apart from a script's."""
    explicit = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if explicit:
        return explicit
    return os.path.join(_CHECKOUT, ".jax_cache", subdir).rstrip(os.sep)


def place_jax_cache(subdir: str = "") -> str:
    """Point this process's persistent compile cache at
    :func:`jax_cache_dir` and return the directory in use. With
    ``JAX_COMPILATION_CACHE_DIR`` set, JAX's own handling of the
    variable stands and nothing is configured here — the ONE place in
    the tree that may set ``jax_compilation_cache_dir``."""
    path = jax_cache_dir(subdir)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path


# ---------------------------------------------------------------- metrics
def _reg():
    from ..obs.metrics import registry
    return registry


def _metrics():
    reg = _reg()
    return {
        "hit": reg.counter(
            "aot_store_hit_total",
            "segment executables served from the AOT store, by "
            "segment/tier (serialized | retrace)"),
        "miss": reg.counter(
            "aot_store_miss_total",
            "AOT store lookups that fell through to a runtime compile, "
            "by segment/reason (absent | corrupt | deserialize | "
            "unfingerprintable | error)"),
        "backfill": reg.counter(
            "aot_store_backfill_total",
            "runtime-compiled executables written back into the store"),
        "build": reg.histogram(
            "aot_build_seconds",
            "lower+compile wall seconds per store build, by segment"),
        "entries": reg.gauge(
            "aot_store_entries", "executables resident in the store"),
        "gc_kept": reg.counter(
            "aot_gc_kept_versions",
            "gc-stale entries spared because a deploy-registry "
            "version still needs them (deploy state or keep-last-N)"),
    }


# ------------------------------------------------------- deploy registry
def _registry_versions(root: str) -> list[dict]:
    """Version records from the deploy-plane registry persisted beside
    the store tree (``serving/deploy.py`` writes ``registry.json``
    there). Read as plain JSON — the gc/list paths must not grow a
    serving import."""
    try:
        with open(os.path.join(root, "registry.json"),
                  encoding="utf-8") as f:
            payload = json.load(f)
    except (OSError, ValueError):
        return []
    recs = payload.get("versions", [])
    return [r for r in recs if isinstance(r, dict) and r.get("name")]


#: registry states that pin a version's entries unconditionally — the
#: live deploy set (mirrors serving.deploy.DEPLOY_STATES): collecting
#: the active version or a rollback target mid-deploy would turn the
#: next flip into a compile storm
_DEPLOY_STATES = ("warming", "candidate", "active", "draining")


def _protected_static_fps(root: str,
                          keep_model_versions: int | None) -> set:
    """Static fingerprints gc must spare: every registry version in a
    deploy state, plus — with ``keep_model_versions=N`` — the last N
    versions by registration order (the operator's rollback horizon)."""
    recs = _registry_versions(root)
    keep: set = set()
    for rec in recs:
        if rec.get("state") in _DEPLOY_STATES:
            keep.update(rec.get("static_fps", []))
    if keep_model_versions:
        ordered = sorted(recs, key=lambda r: r.get("seq", 0))
        for rec in ordered[-int(keep_model_versions):]:
            keep.update(rec.get("static_fps", []))
    return keep


# ----------------------------------------------------------- fingerprints
class Unfingerprintable(ValueError):
    """A stage carries state that cannot be canonically serialized
    (e.g. a raw callable param): its segment must NEVER match a store
    entry — two different callables would otherwise share an
    executable. The segment stays on the runtime-compile path."""


def runtime_versions() -> dict:
    """jax/jaxlib versions WITHOUT importing jax (fingerprint
    computation must stay JAX-free). Absent packages fingerprint as
    "absent" — a store built with jax can never match a process without
    it."""
    import importlib.metadata as md
    out = {}
    for pkg in ("jax", "jaxlib"):
        try:
            out[pkg] = md.version(pkg)
        except md.PackageNotFoundError:
            out[pkg] = "absent"
    return out


def _canon(value):
    """Reduce a param value to a deterministic JSON-able form; raise
    :class:`Unfingerprintable` for anything without one."""
    if value is None or isinstance(value, (bool, int, str)):
        return value
    if isinstance(value, float):
        return repr(value)  # repr round-trips; str() loses precision
    if isinstance(value, np.generic):
        return _canon(value.item())
    if isinstance(value, (list, tuple)):
        return [_canon(v) for v in value]
    if isinstance(value, dict):
        return {str(k): _canon(v) for k, v in sorted(value.items(),
                                                    key=lambda kv:
                                                    str(kv[0]))}
    if isinstance(value, np.ndarray) and value.dtype != object:
        return {"__ndarray__": [str(value.dtype), list(value.shape),
                                hashlib.sha256(
                                    np.ascontiguousarray(value)
                                    .tobytes()).hexdigest()]}
    arr = getattr(value, "__array__", None)
    if arr is not None and hasattr(value, "dtype") \
            and getattr(value.dtype, "kind", "O") != "O":
        # device arrays canonicalize through their host bytes
        return _canon(np.asarray(value))
    raise Unfingerprintable(
        f"param value of type {type(value).__name__} has no canonical "
        "form; its stage cannot be keyed into the AOT store")


def stage_fingerprint(stage) -> dict:
    """One stage's identity: class + every param value (fitted state —
    levels, fill values, idf vectors — lives in params, so a refit
    moves the fingerprint)."""
    entry = {"class": type(stage).__name__}
    params = {}
    get = getattr(stage, "get", None)
    if callable(get) and hasattr(type(stage), "params"):
        for p in type(stage).params():
            params[p.name] = _canon(get(p))
    entry["params"] = params
    return entry


def column_spec(cols: dict) -> list:
    """Ordered (name, dtype, shape) triples for a column dict — works
    on numpy and device arrays alike, no JAX import."""
    return [[c, str(np.dtype(v.dtype)), list(v.shape)]
            for c, v in sorted(cols.items())]


def arg_sig(donated: dict, dropped: dict) -> tuple:
    """Hashable in-memory key for one (donated, dropped) argument pair
    — the per-bucket executable-cache key inside a FusedSegment."""
    def one(cols):
        return tuple((c, str(np.dtype(v.dtype)), tuple(v.shape))
                     for c, v in sorted(cols.items()))
    return one(donated), one(dropped)


def sig_from_spec(donated_spec: list, dropped_spec: list) -> tuple:
    """The same key :func:`arg_sig` yields, rebuilt from a stored
    meta.json spec (warm loading has no arrays in hand)."""
    def one(spec):
        return tuple((c, dt, tuple(shape)) for c, dt, shape in spec)
    return one(donated_spec), one(dropped_spec)


def _sha(payload) -> str:
    return hashlib.sha256(
        json.dumps(payload, sort_keys=True,
                   separators=(",", ":")).encode()).hexdigest()


def mesh_descriptor(mesh) -> list | None:
    """A mesh's fingerprint-relevant identity: axis names + shape.

    On a multi-process (pod) mesh the descriptor additionally carries
    ``[process_count, process_index]``: an executable compiled for a
    2-process (2, 4) mesh addresses only this worker's shard of the
    devices, so a pod worker must never warm-load a single-host build
    of the "same" mesh shape (nor another rank's). Single-host meshes
    keep the bare two-element form, so existing store fingerprints
    stay valid.
    """
    if mesh is None:
        return None
    devs = np.asarray(mesh.devices)
    desc = [list(getattr(mesh, "axis_names", ())), list(devs.shape)]
    procs = sorted({getattr(d, "process_index", 0) for d in devs.flat})
    if procs != [0]:
        import jax
        desc.append([len(procs), int(jax.process_index())])
    return desc


def _canon_rules(rules) -> list | None:
    """Partition rules' fingerprint form: (pattern, spec) pairs as
    deterministic strings (PartitionSpec reprs are stable). Rules
    change the compiled program's shardings, so they MUST move the
    key."""
    if not rules:
        return None
    try:
        return [[str(p), repr(s)] for p, s in rules]
    except (TypeError, ValueError) as e:
        raise Unfingerprintable(
            f"partition rules have no canonical form: {e}") from e


def segment_static_key(stages, *, no_donate=(), expected_host=(),
                       mesh=None, donate: bool = True, rules=None,
                       platform: str = "cpu",
                       versions: dict | None = None) -> dict:
    """Everything that decides WHAT program a segment lowers to, minus
    input shapes — incl. the donation flag and partition rules, which
    change buffer aliasing / shardings in the executable. Raises
    :class:`Unfingerprintable` when any stage cannot be
    canonicalized."""
    return {
        "v": STORE_VERSION,
        "stages": [stage_fingerprint(s) for s in stages],
        "no_donate": sorted(no_donate),
        "expected_host": sorted(expected_host),
        "mesh": mesh_descriptor(mesh),
        "donate": bool(donate),
        "rules": _canon_rules(rules),
        "platform": platform,
        "versions": versions if versions is not None
        else runtime_versions(),
    }


def fingerprints(static_key: dict, donated_spec: list,
                 dropped_spec: list) -> tuple[str, str]:
    """→ (static_fp, full_fp). The static fp groups every padding
    bucket of one segment program; the full fp is one executable."""
    static_fp = _sha(static_key)
    full_fp = _sha({"static": static_fp, "donated": donated_spec,
                    "dropped": dropped_spec})
    return static_fp, full_fp


def _backend_platform() -> str:
    import jax
    return jax.default_backend()


def segment_fingerprints(segment, donated: dict,
                         dropped: dict) -> tuple[str, str, dict]:
    """Fingerprints for a live :class:`~.compile.FusedSegment` and one
    argument pair (requires jax for the backend platform only)."""
    key = segment_static_key(
        segment.stages, no_donate=segment.no_donate,
        expected_host=segment.expected_host, mesh=segment.mesh,
        donate=segment.donate, rules=segment.rules,
        platform=_backend_platform())
    dspec, pspec = column_spec(donated), column_spec(dropped)
    static_fp, full_fp = fingerprints(key, dspec, pspec)
    return static_fp, full_fp, {"static_key": key, "donated": dspec,
                                "dropped": pspec}


def _zeros_from_spec(spec: list) -> dict:
    return {c: np.zeros(tuple(shape), np.dtype(dt))
            for c, dt, shape in spec}


# ------------------------------------------------------------- the store
class AotStore:
    """On-disk executable store, content-addressed by full fingerprint.

    Writes are atomic (tmp dir + ``os.replace``, the
    ``dl/checkpoint`` discipline) so a killed build never leaves a
    half-entry a loader could trust; every ``exe.bin`` carries its
    sha256 in ``meta.json`` and a mismatch is a loud ``corrupt`` miss,
    never a deserialization attempt."""

    def __init__(self, root: str | None = None):
        self.root = root or store_root()
        self._lock = threading.Lock()
        # metrics live in the process-wide registry like every other
        # subsystem's: one scrape surface per process
        self._m = _metrics()
        # entry count cache: save/invalidate adjust it incrementally
        # so the request-path backfill never walks the whole store
        # (None = not yet counted)
        self._n_entries: int | None = None

    # -- layout --------------------------------------------------------
    def entry_dir(self, full_fp: str) -> str:
        return os.path.join(self.root, full_fp[:2], full_fp)

    def entries(self) -> list[dict]:
        """Every readable meta.json in the store (unreadable entries
        are skipped — they can only ever be misses anyway)."""
        out = []
        if not os.path.isdir(self.root):
            return out
        for shard in sorted(os.listdir(self.root)):
            sdir = os.path.join(self.root, shard)
            if len(shard) != 2 or not os.path.isdir(sdir):
                continue
            for fp in sorted(os.listdir(sdir)):
                # only finished entries: full fingerprints are 64-hex
                # dir names, so in-flight .tmp-* dirs (a concurrent
                # build mid-os.replace) and any leaked junk never read
                # as corrupt entries or count in stats/gc
                if len(fp) != 64 or fp.startswith("."):
                    continue
                meta = self._read_meta(os.path.join(sdir, fp))
                if meta is not None:
                    out.append(meta)
        return out

    def entries_for(self, static_fp: str) -> list[dict]:
        return [m for m in self.entries()
                if m.get("static_fp") == static_fp]

    def _read_meta(self, edir: str) -> dict | None:
        try:
            with open(os.path.join(edir, _META), encoding="utf-8") as f:
                meta = json.load(f)
            meta["_dir"] = edir
            return meta
        except (OSError, ValueError):
            return None

    def _count_entries(self, delta: int | None = None) -> None:
        """Keep the entry gauge (and its cache) current. ``delta``
        adjusts incrementally (save/invalidate — no store walk on the
        request path); ``None`` forces a recount (gc)."""
        with self._lock:
            if delta is None or self._n_entries is None:
                self._n_entries = len(self.entries())
                if delta is not None:
                    delta = 0  # recount already includes the change
            self._n_entries = max(self._n_entries + (delta or 0), 0)
            self._m["entries"].set(self._n_entries)

    # -- write ---------------------------------------------------------
    def save(self, *, full_fp: str, static_fp: str, segment_name: str,
             meta_extra: dict, blob: bytes | None,
             hlo_text: str | None) -> None:
        """Atomically publish one entry. ``blob=None`` writes a
        retrace-tier entry (meta + HLO text only)."""
        meta = {
            "store_version": STORE_VERSION,
            "full_fp": full_fp,
            "static_fp": static_fp,
            "segment": segment_name,
            "tier": "serialized" if blob is not None else "retrace",
            "exe_sha256": hashlib.sha256(blob).hexdigest()
            if blob is not None else None,
        }
        meta.update(meta_extra)
        final = self.entry_dir(full_fp)
        os.makedirs(os.path.dirname(final), exist_ok=True)
        tmp = tempfile.mkdtemp(dir=os.path.dirname(final),
                               prefix=".tmp-")
        try:
            with open(os.path.join(tmp, _META), "w",
                      encoding="utf-8") as f:
                json.dump(meta, f, indent=1, sort_keys=True)
            if blob is not None:
                with open(os.path.join(tmp, _EXE), "wb") as f:
                    f.write(blob)
            if hlo_text is not None:
                with open(os.path.join(tmp, _HLO), "w",
                          encoding="utf-8") as f:
                    f.write(hlo_text)
            with self._lock:
                existed = os.path.isdir(final)
                if existed:
                    shutil.rmtree(final, ignore_errors=True)
                os.replace(tmp, final)
        except Exception:
            # ANY failure (not just OSError — e.g. a meta value json
            # cannot encode) must reclaim the tmp dir, or it lingers
            # in the shard forever
            shutil.rmtree(tmp, ignore_errors=True)
            raise
        self._count_entries(0 if existed else 1)

    def invalidate(self, full_fp: str) -> bool:
        final = self.entry_dir(full_fp)
        with self._lock:
            if not os.path.isdir(final):
                return False
            shutil.rmtree(final, ignore_errors=True)
        self._count_entries(-1)
        return True

    def gc(self, keep_static: set[str] | None = None,
           keep_versions: bool = True,
           keep_model_versions: int | None = None) -> list[str]:
        """Remove stale entries: anything whose static fingerprint is
        not in ``keep_static`` (when given), plus — with
        ``keep_versions`` — anything built against a different
        jax/jaxlib than this process would fingerprint (those can never
        match again; they are dead weight).

        Deploy-plane protection (``serving/deploy.py``,
        ``registry.json`` beside the tree): an entry a registry version
        in a deploy state (warming/candidate/active/draining) still
        points at is NEVER removed — whatever keep_static says — and
        ``keep_model_versions=N`` (CLI ``gc --keep-versions N``)
        additionally pins the last N registered versions, so a rollback
        target survives every gc that runs mid-deploy. Spared entries
        count in ``aot_gc_kept_versions``."""
        versions = runtime_versions()
        protected = _protected_static_fps(self.root,
                                          keep_model_versions)
        removed, kept = [], 0
        for meta in self.entries():
            stale = False
            if keep_static is not None \
                    and meta.get("static_fp") not in keep_static:
                stale = True
            if keep_versions and meta.get("versions") not in (
                    None, versions):
                stale = True
            if stale and meta.get("static_fp") in protected:
                kept += 1
                continue
            if stale:
                shutil.rmtree(meta["_dir"], ignore_errors=True)
                removed.append(meta["full_fp"])
        if kept:
            self._m["gc_kept"].inc(kept)
            _LOG.info("aot store gc: kept %d entries pinned by the "
                      "deploy registry", kept)
        if removed:
            _LOG.info("aot store gc: removed %d stale entries",
                      len(removed))
        self._count_entries()
        return removed

    # -- read ----------------------------------------------------------
    def _checked_blob(self, meta: dict) -> bytes | None:
        """exe.bin bytes iff present AND matching the recorded sha256;
        a mismatch deletes nothing (evidence) but reads as corrupt."""
        path = os.path.join(meta["_dir"], _EXE)
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except OSError:
            return None
        if hashlib.sha256(blob).hexdigest() != meta.get("exe_sha256"):
            return None
        return blob

    def load_entry(self, meta: dict, *, segment=None):
        """One stored entry → a callable executable, or None with the
        miss reason counted. ``segment`` enables the retrace tier (the
        traced body is needed to re-lower)."""
        name = meta.get("segment", "?")
        if meta.get("tier") == "serialized":
            blob = self._checked_blob(meta)
            if blob is None:
                self._m["miss"].inc(1, segment=name, reason="corrupt")
                _LOG.warning(
                    "aot store entry %s for segment %s is corrupt "
                    "(checksum mismatch or unreadable exe.bin); "
                    "falling back to runtime compile",
                    meta.get("full_fp", "?")[:12], name)
                return None
            from ..parallel import compat
            try:
                exe = compat.deserialize_compiled(blob)
            except Exception:
                self._m["miss"].inc(1, segment=name,
                                    reason="deserialize")
                _LOG.warning(
                    "aot store entry %s for segment %s failed to "
                    "deserialize (jaxlib/backend drift?); falling back "
                    "to runtime compile", meta.get("full_fp", "?")[:12],
                    name, exc_info=True)
                return None
            self._m["hit"].inc(1, segment=name, tier="serialized")
            return exe
        # retrace tier: the store records the program identity + specs;
        # compiling from the traced body at WARM time still moves the
        # cost out of request latency (the tier exists for jax builds
        # without serialize_executable)
        if segment is None:
            self._m["miss"].inc(1, segment=name, reason="deserialize")
            return None
        try:
            donated = _zeros_from_spec(meta["donated"])
            dropped = _zeros_from_spec(meta["dropped"])
            fn = segment._ensure_fn(donated, dropped)
            exe = fn.lower(donated, dropped).compile()
        except Exception:
            self._m["miss"].inc(1, segment=name, reason="error")
            _LOG.warning("aot retrace-tier load failed for segment %s",
                         name, exc_info=True)
            return None
        self._m["hit"].inc(1, segment=name, tier="retrace")
        return exe

    # -- the segment-facing surface -------------------------------------
    def load_or_compile(self, segment, donated: dict, dropped: dict,
                        *, building: bool = False, _fps=None):
        """The FusedSegment request path: store hit → deserialized
        executable; miss → LOUD counter, then compile-and-backfill so
        the next fresh process hits. Returns None only for segments
        that cannot be fingerprinted (they keep the plain jit path).
        ``building=True`` (the build CLI) treats an absent entry as the
        job, not a miss — no counter, no warning. ``_fps`` reuses a
        caller's already-computed fingerprints (hashing every fitted
        param array is the expensive part — don't pay it twice)."""
        try:
            if _fps is None:
                _fps = segment_fingerprints(segment, donated, dropped)
            static_fp, full_fp, specs = _fps
        except Unfingerprintable as e:
            self._m["miss"].inc(1, segment=segment.name,
                                reason="unfingerprintable")
            _LOG.warning("segment %s is not AOT-eligible: %s",
                         segment.name, e)
            return None
        meta = self._read_meta(self.entry_dir(full_fp))
        if meta is not None:
            exe = self.load_entry(meta, segment=segment)
            if exe is not None:
                return exe
            # corrupt/deserialize miss already counted by load_entry
        elif not building:
            self._m["miss"].inc(1, segment=segment.name,
                                reason="absent")
            _LOG.warning(
                "aot store miss (absent) for segment %s bucket %s — "
                "compiling at runtime and backfilling; run the build "
                "CLI to cover this (route, bucket)", segment.name,
                [list(v.shape) for v in donated.values()] or
                [list(v.shape) for v in dropped.values()])
        return self.build_segment(segment, donated, dropped,
                                  _fps=(static_fp, full_fp, specs),
                                  backfill=not building)

    def build_segment(self, segment, donated: dict, dropped: dict, *,
                      _fps=None, backfill: bool = False):
        """lower+compile one segment × bucket and publish it. The build
        CLI's unit of work; also the miss path's backfill."""
        import time as _time
        if _fps is None:
            static_fp, full_fp, specs = segment_fingerprints(
                segment, donated, dropped)
        else:
            static_fp, full_fp, specs = _fps
        fn = segment._ensure_fn(donated, dropped)
        t0 = _time.perf_counter()
        lowered = fn.lower(donated, dropped)
        compiled = lowered.compile()
        self._m["build"].observe(_time.perf_counter() - t0,
                                 segment=segment.name)
        try:
            hlo = lowered.as_text()
        except Exception:
            hlo = None
        from ..parallel import compat
        # analytic cost attribution (obs.attribution, ISSUE 20): every
        # built program carries its cost_analysis flops/bytes in
        # meta.json, and exports its roofline placement now — warm
        # loads re-export from the persisted pair without re-analyzing
        cost = compat.cost_analysis(compiled)
        if cost is not None:
            from ..obs.attribution import cost_attribution
            cost_attribution.record_program(
                segment.name, cost["flops"], cost["bytes"],
                service=segment.name.split(":", 1)[0])
        blob = None
        try:
            blob = compat.serialize_compiled(compiled)
        except Exception:
            _LOG.warning(
                "executable serialization failed for segment %s; "
                "storing a retrace-tier entry (warm loads will "
                "re-lower at boot, not at request time)",
                segment.name, exc_info=True)
        try:
            self.save(full_fp=full_fp, static_fp=static_fp,
                      segment_name=segment.name,
                      meta_extra={"donated": specs["donated"],
                                  "dropped": specs["dropped"],
                                  "versions":
                                      specs["static_key"]["versions"],
                                  "platform":
                                      specs["static_key"]["platform"],
                                  **({"cost": cost} if cost is not None
                                     else {})},
                      blob=blob, hlo_text=hlo)
            if backfill:
                self._m["backfill"].inc(1, segment=segment.name)
        except OSError:
            _LOG.warning("aot store write failed for segment %s",
                         segment.name, exc_info=True)
        return compiled

    def warm_segment(self, segment, entries: list | None = None) -> int:
        """Preload every stored bucket of one segment into its
        in-memory executable cache — the scale-up warm boot. Returns
        the number of executables now resident. ``entries`` lets a
        multi-segment warm (maybe_warm) walk the store ONCE and share
        the listing."""
        try:
            key = segment_static_key(
                segment.stages, no_donate=segment.no_donate,
                expected_host=segment.expected_host, mesh=segment.mesh,
                donate=segment.donate, rules=segment.rules,
                platform=_backend_platform())
        except Unfingerprintable:
            return 0
        static_fp = _sha(key)
        if entries is None:
            entries = self.entries()
        n = 0
        for meta in entries:
            if meta.get("static_fp") != static_fp:
                continue
            sig = sig_from_spec(meta.get("donated", []),
                                meta.get("dropped", []))
            if segment._exes.get(sig) is not None:
                continue
            exe = self.load_entry(meta, segment=segment)
            if exe is not None:
                try:
                    # one throwaway dispatch on spec-shaped zeros: a
                    # deserialized Compiled builds its argument-
                    # processing path lazily on first call, and that
                    # setup belongs in the warm boot, not in the first
                    # request's latency (segment bodies are pure by
                    # the traceable-stage contract, so a zeros call
                    # has no side effects)
                    exe(_zeros_from_spec(meta.get("donated", [])),
                        _zeros_from_spec(meta.get("dropped", [])))
                except Exception:
                    _LOG.warning(
                        "aot warm dispatch failed for segment %s; the "
                        "first request will pay the call-path setup",
                        segment.name, exc_info=True)
                segment._exes[sig] = exe
                n += 1
                # re-export the entry's persisted analytic cost (no
                # re-analysis — a deserialized Compiled may not even
                # support cost_analysis): warmed processes report the
                # same roofline gauges the builder did
                cost = meta.get("cost")
                if isinstance(cost, dict):
                    from ..obs.attribution import cost_attribution
                    cost_attribution.record_program(
                        segment.name,
                        cost.get("flops", 0.0), cost.get("bytes", 0.0),
                        service=segment.name.split(":", 1)[0],
                        platform=meta.get("platform") or None)
        return n

    def stats(self) -> dict:
        entries = self.entries()
        return {
            "root": self.root,
            "entries": len(entries),
            "segments": sorted({m.get("segment", "?")
                                for m in entries}),
            "tiers": {t: sum(1 for m in entries
                             if m.get("tier") == t)
                      for t in ("serialized", "retrace")},
        }


# ------------------------------------------------- process-wide activation
_active: AotStore | None = None
_active_lock = threading.Lock()


def install(store: AotStore | str | None = None) -> AotStore:
    """Make a store the process-wide active one: every FusedSegment
    consults it on first execution of a novel bucket."""
    global _active
    with _active_lock:
        if not isinstance(store, AotStore):
            store = AotStore(store)
        _active = store
        return store


def uninstall() -> None:
    global _active
    with _active_lock:
        _active = None


def active_store() -> AotStore | None:
    return _active


# ------------------------------------------------------------ warm loading
def _owned_by_us(path: str) -> bool:
    getuid = getattr(os, "getuid", None)
    if getuid is None:  # platforms without uids: nothing to check
        return True
    try:
        return os.stat(path).st_uid == getuid()
    except OSError:
        return False


def _segments_of(obj):
    """Yield every FusedSegment reachable in a transform object: a
    CompiledPipeline, a stage list, or a DSL ``run`` closure that
    carries its ``stages``."""
    from .compile import CompiledPipeline, FusedSegment
    if obj is None:
        return
    if isinstance(obj, FusedSegment):
        yield obj
        return
    if isinstance(obj, CompiledPipeline):
        for item in obj.plan:
            if isinstance(item, FusedSegment):
                yield item
        return
    if isinstance(obj, (list, tuple)):
        for o in obj:
            yield from _segments_of(o)
        return
    # a DSL ``run`` closure carries its chain as ``run.stages`` (a real
    # list — NOT the Param descriptor a PipelineStage's class attribute
    # resolves to, hence the isinstance gate)
    stages = getattr(obj, "stages", None)
    if isinstance(stages, (list, tuple)):
        yield from _segments_of(list(stages))


def maybe_warm(obj, service: str = "") -> int:
    """Warm-load AOT executables for every fused segment reachable in
    ``obj``. Uses the installed store, or auto-installs one when the
    configured root already exists on disk (so a fresh worker process
    boots hot with zero code changes once the build CLI has run).
    Returns the number of executables loaded; never raises — a warm
    failure must not stop a server from starting cold."""
    try:
        store = active_store()
        if store is None:
            root = store_root()
            if not os.path.isdir(root):
                return 0
            if not _owned_by_us(root):
                # deserialization is pickle: auto-trusting a root some
                # OTHER uid controls would execute their code at boot.
                # An operator who really means it can aot.install() it
                # explicitly.
                _LOG.warning(
                    "aot store root %s is not owned by this user; "
                    "refusing to auto-install it (install() it "
                    "explicitly to override)", root)
                return 0
            store = install(AotStore(root))
        n = 0
        listing = None  # one store walk shared by every segment
        for seg in _segments_of(obj):
            if listing is None:
                listing = store.entries()
            n += store.warm_segment(seg, entries=listing)
        if n:
            _LOG.info("aot warm start%s: %d executable(s) loaded from "
                      "%s", f" [{service}]" if service else "", n,
                      store.root)
        return n
    except Exception:
        _LOG.warning("aot warm start failed; serving will compile at "
                     "runtime", exc_info=True)
        return 0


# ------------------------------------------------------ build registrations
#: service → builder() -> {"stages": [...], "example": DataFrame,
#: "buckets": (int, ...), "mesh": ..., "rules": ...}
_BUILDERS: dict[str, callable] = {}
_builders_lock = threading.Lock()


def register_buildable(service: str, builder) -> None:
    """Register a serving pipeline for the build CLI. ``builder`` is a
    zero-arg callable returning the dict above — called lazily so
    registration at import time stays free (and JAX-free)."""
    with _builders_lock:
        _BUILDERS[service] = builder


def buildable_services() -> list[str]:
    with _builders_lock:
        return sorted(_BUILDERS)


def _resize_example(df, n: int):
    """Tile/truncate an example frame to ``n`` rows — one padding
    bucket's worth of representative columns."""
    from .dataframe import DataFrame
    data = {}
    for c in df.columns:
        col = df[c]
        host = np.asarray(col)
        if host.dtype == object:
            reps = -(-n // max(len(host), 1))
            tiled = np.concatenate([host] * reps)[:n]
            out = np.empty(n, object)
            out[:] = list(tiled)
            data[c] = out
        else:
            reps = -(-n // max(len(host), 1))
            data[c] = np.concatenate([host] * reps, axis=0)[:n]
    return DataFrame(data)


def build_pipeline(cp, example_df, store: AotStore) -> list[dict]:
    """Build every fused segment of one CompiledPipeline for the
    example's bucket, installing the executables in place (the plan is
    executed on the example so downstream segments see the traced
    layout, exactly like compile-time schema propagation)."""
    from .compile import FusedSegment, trace_columns
    records = []
    cur = example_df
    for item in cp.plan:
        if isinstance(item, FusedSegment):
            num = trace_columns(cur)
            donated, dropped = item._split(num)
            try:
                static_fp, full_fp, specs = segment_fingerprints(
                    item, donated, dropped)
                exe = store.load_or_compile(
                    item, donated, dropped, building=True,
                    _fps=(static_fp, full_fp, specs))
                if exe is not None:
                    item._exes[arg_sig(donated, dropped)] = exe
                records.append({
                    "segment": item.name, "static_fp": static_fp,
                    "full_fp": full_fp,
                    "built": exe is not None,
                    "stages": [type(s).__name__ for s in item.stages]})
            except Unfingerprintable as e:
                records.append({"segment": item.name, "built": False,
                                "error": str(e)})
        cur = item.run(cur)
    return records


def _bucket_build_order(service: str, buckets) -> list[int]:
    """Cost-model build planner (ISSUE 12): order a service's padding
    buckets by predicted traffic value — observed FeatureLog request
    share × the learned model's predicted execute cost — so an
    interrupted or time-boxed build compiles the hot path first.
    Deterministic ascending order when nothing has been learned yet
    (a fresh process, or perf unavailable)."""
    try:
        from ..perf.costmodel import bucket_build_priority
        ranked = bucket_build_priority(service, buckets)
    except Exception:
        ranked = []
    if ranked:
        _LOG.info("AOT build order for %r by predicted traffic value: "
                  "%s", service, ranked)
        return ranked
    return sorted({int(x) for x in buckets})


def build_registered(service: str | None = None,
                     store: AotStore | None = None,
                     log=print) -> dict:
    """The build CLI body: for every registered service × padding
    bucket, compile the pipeline's fused segments into the store —
    most-valuable buckets first (:func:`_bucket_build_order`).
    Returns a report incl. the AOT coverage of TRACEABLE stages (from
    ``analysis/traceability.json``)."""
    from .compile import compile_pipeline
    store = store or active_store() or install(AotStore())
    services = [service] if service else buildable_services()
    report = {"root": store.root, "services": {}, "entries": []}
    built_stage_classes: set[str] = set()
    for svc in services:
        with _builders_lock:
            builder = _BUILDERS.get(svc)
        if builder is None:
            raise KeyError(f"no AOT builder registered for {svc!r} "
                           f"(registered: {buildable_services()})")
        spec = builder()
        buckets = tuple(spec.get("buckets") or
                        (len(spec["example"]),))
        svc_records = []
        build_order = _bucket_build_order(svc, buckets)
        for b in build_order:
            example = _resize_example(spec["example"], b)
            cp = compile_pipeline(
                spec["stages"], example, mesh=spec.get("mesh"),
                rules=spec.get("rules"), service=svc)
            recs = build_pipeline(cp, example, store)
            for r in recs:
                r["bucket"] = b
                built_stage_classes.update(r.get("stages", ()))
                log(f"  [{svc}] bucket={b} {r['segment']} "
                    f"{'OK ' + r['full_fp'][:12] if r.get('built') else 'SKIP ' + r.get('error', '')}")
            svc_records.extend(recs)
        report["services"][svc] = {
            "buckets": sorted(set(int(x) for x in buckets)),
            "build_order": build_order,
            "segments": svc_records}
        report["entries"].extend(svc_records)
    report["coverage"] = _traceable_coverage(built_stage_classes)
    return report


def _traceable_coverage(built_classes: set[str]) -> dict:
    """AOT coverage of the TRACEABLE stage population —
    ``analysis/traceability.json`` is the work-list this store
    consumes, so the build report says how much of it is covered."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "analysis", "traceability.json")
    try:
        with open(path, encoding="utf-8") as f:
            tr = json.load(f)
    except (OSError, ValueError):
        return {"traceable": 0, "covered": 0, "missing": []}
    traceable = sorted(s["stage"] for s in tr.get("stages", ())
                       if s.get("classification") == "TRACEABLE")
    covered = sorted(s for s in traceable if s in built_classes)
    return {"traceable": len(traceable), "covered": len(covered),
            "missing": [s for s in traceable if s not in covered]}


# -------------------------------------------------------------- selftest
_SELFTEST_SERVICE = "__selftest__"


def _selftest_builder() -> dict:
    """A deterministic all-param pipeline (no callables → fully
    fingerprintable) used by the CI build-then-load round trip."""
    from .dataframe import DataFrame
    from ..featurize import CleanMissingData, VectorAssembler
    from ..featurize.vector import OneHotEncoderModel

    n, width = 8, 4
    img = (np.arange(n * width, dtype=np.float32)
           .reshape(n, width) / 7.0)
    aux = np.arange(n, dtype=np.float32)
    aux[::3] = np.nan
    cat = (np.arange(n) % 3).astype(np.int32)
    df = DataFrame({"img": img, "aux": aux, "cat": cat})
    clean = CleanMissingData(inputCols=["aux"],
                             cleaningMode="Mean").fit(df)
    stages = [
        clean,
        OneHotEncoderModel(inputCol="cat", outputCol="onehot",
                           categorySize=3, handleInvalid="keep"),
        VectorAssembler(inputCols=["img", "aux", "onehot"],
                        outputCol="features", handleInvalid="keep"),
    ]
    return {"stages": stages, "example": df, "buckets": (4, 8)}


def register_selftest() -> None:
    register_buildable(_SELFTEST_SERVICE, _selftest_builder)


def _verify(root: str, service: str) -> int:
    """The load half of the round trip: fresh plan, warm from the
    store, steady-state declared BEFORE the first request — then the
    run must show zero runtime compiles, ≥1 store hit, and output
    bit-equal to a runtime-compiled plan."""
    from .compile import compile_pipeline
    from ..obs.profile import compile_tracker

    if service == _SELFTEST_SERVICE:
        register_selftest()
    with _builders_lock:
        builder = _BUILDERS.get(service)
    if builder is None:
        print(f"verify: no builder registered for {service!r}")
        return 2
    spec = builder()
    store = install(AotStore(root))
    reg = _reg()

    # reference: runtime-compiled fused output (store NOT consulted)
    uninstall()
    ref_cp = compile_pipeline(spec["stages"], spec["example"],
                              service=service + "-ref")
    ref = ref_cp.transform(spec["example"])

    install(store)
    before = {k: v for k, v in reg.snapshot().items()
              if k.startswith("aot_store_hit_total")}
    cp = compile_pipeline(spec["stages"], spec["example"],
                          service=service)
    warmed = maybe_warm(cp, service=service)
    compile_tracker.mark_steady()
    out = cp.transform(spec["example"])
    runtime = compile_tracker.runtime_compiles()
    compile_tracker.unmark_steady()
    ok = True
    if warmed < 1:
        print(f"verify FAIL: warm start loaded {warmed} executables")
        ok = False
    if runtime:
        print(f"verify FAIL: {runtime} runtime compile(s) after "
              f"steady state: {compile_tracker.runtime_compiled()}")
        ok = False
    for c in ref.columns:
        a, b = np.asarray(ref[c]), np.asarray(out[c])
        if a.shape != b.shape or not np.array_equal(a, b):
            print(f"verify FAIL: column {c!r} differs from the "
                  "runtime-compiled reference")
            ok = False
    hits = sum(v for k, v in reg.snapshot().items()
               if k.startswith("aot_store_hit_total")) - \
        sum(before.values())
    print(f"verify: warmed={warmed} runtime_compiles={runtime} "
          f"hits={hits} columns_equal={ok}")
    return 0 if ok else 1


def _cli(argv=None) -> int:
    import argparse
    import subprocess
    import sys

    ap = argparse.ArgumentParser(
        prog="python -m mmlspark_tpu.core.aot",
        description="AOT executable store: build / list / gc / "
                    "selftest / verify")
    sub = ap.add_subparsers(dest="cmd", required=True)
    b = sub.add_parser("build", help="compile registered pipelines "
                       "into the store")
    b.add_argument("--import", dest="imports", action="append",
                   default=[], metavar="MODULE",
                   help="module(s) to import first (they call "
                        "aot.register_buildable)")
    b.add_argument("--service", default=None)
    b.add_argument("--root", default=None)
    ls = sub.add_parser("list", help="print store entries (and the "
                        "deploy registry's versions, when present)")
    ls.add_argument("--root", default=None)
    g = sub.add_parser("gc", help="drop version-stale entries (and "
                       "anything not matching --keep-static); "
                       "registry versions in a deploy state are "
                       "always spared")
    g.add_argument("--root", default=None)
    g.add_argument("--keep-static", action="append", default=None,
                   metavar="FP")
    g.add_argument("--keep-versions", type=int, default=None,
                   metavar="N",
                   help="additionally pin the last N deploy-registry "
                        "versions' entries (rollback horizon); spared "
                        "entries count in aot_gc_kept_versions")
    st = sub.add_parser("selftest", help="build-then-load round trip "
                        "in two CPU-pinned subprocesses (CI job)")
    st.add_argument("--root", default=None)
    v = sub.add_parser("verify", help="warm-load a service from the "
                       "store and assert zero runtime compiles")
    v.add_argument("--root", required=True)
    v.add_argument("--service", required=True)
    v.add_argument("--import", dest="imports", action="append",
                   default=[], metavar="MODULE")
    args = ap.parse_args(argv)

    if args.cmd == "list":
        store = AotStore(args.root)
        entries = store.entries()
        for m in entries:
            print(f"{m['full_fp'][:16]} {m.get('tier', '?'):10s} "
                  f"{m.get('segment', '?')}")
        # deploy registry (serving/deploy.py persists registry.json
        # beside the tree): version names, fingerprints, and per-bucket
        # built/warm state — the operator's pre-flip checklist
        recs = _registry_versions(store.root)
        if recs:
            by_static: dict = {}
            for m in entries:
                by_static.setdefault(m.get("static_fp"), []).append(m)
            print("registry versions:")
            for rec in sorted(recs, key=lambda r: r.get("seq", 0)):
                fps = rec.get("static_fps", [])
                print(f"  {rec['name']:20s} "
                      f"{rec.get('state', '?'):10s} "
                      f"warmed={rec.get('warmed', 0)} "
                      f"fps={','.join(fp[:12] for fp in fps) or '-'}")
                for fp in fps:
                    for m in by_static.get(fp, []):
                        spec = m.get("donated") or []
                        bucket = spec[0][2][0] if spec and \
                            spec[0][2] else "?"
                        print(f"    bucket={bucket:<6} "
                              f"{m.get('tier', '?'):10s} "
                              f"{m['full_fp'][:16]}")
        print(json.dumps(store.stats(), indent=1))
        return 0

    if args.cmd == "gc":
        store = AotStore(args.root)
        keep = set(args.keep_static) if args.keep_static else None
        removed = store.gc(keep_static=keep,
                           keep_model_versions=args.keep_versions)
        print(f"gc: removed {len(removed)} entries; "
              f"{store.stats()['entries']} remain")
        return 0

    if args.cmd == "build":
        import importlib
        for mod in args.imports:
            importlib.import_module(mod)
        if args.service == _SELFTEST_SERVICE or (
                not args.imports and not buildable_services()):
            register_selftest()
        store = AotStore(args.root)
        report = build_registered(args.service, store)
        cov = report["coverage"]
        print(f"build: {len(report['entries'])} entries in "
              f"{store.root}; traceable-stage coverage "
              f"{cov['covered']}/{cov['traceable']}")
        return 0

    if args.cmd == "verify":
        import importlib
        for mod in args.imports:
            importlib.import_module(mod)
        return _verify(args.root, args.service)

    if args.cmd == "selftest":
        from .utils import cpu_child_env
        root = args.root or tempfile.mkdtemp(
            prefix="mmlspark_tpu_aot_selftest_")
        env = cpu_child_env()
        rc = subprocess.call(
            [sys.executable, "-m", "mmlspark_tpu.core.aot", "build",
             "--service", _SELFTEST_SERVICE, "--root", root], env=env)
        if rc:
            print("selftest FAILED at build")
            return rc
        rc = subprocess.call(
            [sys.executable, "-m", "mmlspark_tpu.core.aot", "verify",
             "--service", _SELFTEST_SERVICE, "--root", root], env=env)
        print("selftest " + ("OK" if rc == 0 else "FAILED at verify"))
        if args.root is None:
            shutil.rmtree(root, ignore_errors=True)
        return rc
    return 2


if __name__ == "__main__":  # pragma: no cover
    # `python -m mmlspark_tpu.core.aot` executes this file as
    # ``__main__`` — a SECOND module object with its own _BUILDERS.
    # Delegate to the canonical import so `--import`ed app modules
    # (which call mmlspark_tpu.core.aot.register_buildable) and the
    # CLI share one registry.
    from mmlspark_tpu.core.aot import _cli as _canonical_cli
    raise SystemExit(_canonical_cli())
