"""Compile the main path's Pallas kernels, and the two whole programs
that carry them, for the real chip — described (``v5e:2x2``), not
attached. The TPU's compiler is installed here, so a slice that is not
aligned to the tiling, a kernel that asks for too much fast memory or a
program that does not fit HBM is refused here at no chip time. Nothing
runs: this says nothing about results or speed (``chip_smoke.py`` does,
on the chip).

The only file that describes the chip. The topology is described inside
a fixture, after a test of this file has started — never at import — so
that every xdist worker collects the same tests and only the worker that
is given this file loads the TPU's library.
"""

import os

import numpy as np
import pytest

KERNEL = "tpu_custom_call"


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable compiled for a described chip is written to the
    # persistent cache but cannot be read back without one: keep the
    # cache off around these compiles
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def steer_tpu(monkeypatch):
    """Whole programs ask ``target_platform()`` which path to build, and
    here it answers ``cpu``: steer it from the test (not through an
    option of the program) so that the chip's path is what is lowered."""
    import mmlspark_tpu.dl.pallas_gated_delta as gated_delta
    import mmlspark_tpu.dl.pallas_lightning as lightning
    import mmlspark_tpu.dl.pallas_paged_attention as paged
    import mmlspark_tpu.utils.platform as plat
    monkeypatch.setattr(plat, "target_platform", lambda: "tpu")
    monkeypatch.setattr(paged, "target_platform", lambda: "tpu")
    monkeypatch.setattr(lightning, "target_platform", lambda: "tpu")
    monkeypatch.setattr(gated_delta, "target_platform", lambda: "tpu")


def _sds(shape, dtype, sharding):
    import jax
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _lower_engine_programs(engine, w, sharding, params, dparams, pools,
                           dpools) -> dict:
    """The engine's programs lowered from shapes alone: the decode step,
    both prefill programs of window ``w`` and, where a window rides with
    the decoding rows, the step program of that window."""
    import jax

    def lower(decode, window, head=True):
        dec, win = jax.tree.map(
            lambda a: _sds(a.shape, a.dtype, sharding),
            engine.programs.blank(decode, window))
        return engine.programs.get(decode, window, head).lower(
            params, dparams, pools, dpools, dec, win)

    out = {"decode": lower(True, None)}
    for head in (True, False):
        out[f"prefill_w{w}_head{head:d}"] = lower(False, w, head)
    if engine.prefiller.rider is not None:
        out[f"step_w{w}"] = lower(True, w)
    return out


def _compile(fn, *args):
    import jax
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert KERNEL in text, "no Mosaic kernel in the compiled program"
    return compiled, text


@pytest.mark.parametrize("n,F,B", [(500_000, 28, 256), (4097, 5, 16)],
                         ids=["higgs-500kx28-b256", "ragged-4097x5-b16"])
def test_hist_pallas(one_chip, n, F, B):
    import jax.numpy as jnp

    from mmlspark_tpu.lightgbm.pallas_hist import hist_pallas
    _compile(lambda b, v: hist_pallas(b, v, num_bins=B, interpret=False),
             _sds((n, F), jnp.uint8, one_chip),
             _sds((n, 3), jnp.float32, one_chip))


@pytest.mark.parametrize("mode", ["forward", "causal", "grad-pallas"])
def test_flash_attention(one_chip, mode):
    """B8 H8 T2048 D64 bf16 — the encoder phase's attention shape."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.dl.pallas_attention import flash_attention
    qkv = [_sds((8, 8, 2048, 64), jnp.bfloat16, one_chip)] * 3
    if mode == "grad-pallas":
        def fn(q, k, v):
            return jax.grad(lambda q, k, v: flash_attention(
                q, k, v, interpret=False, bwd_impl="pallas").astype(
                    jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)
    else:
        def fn(q, k, v):
            return flash_attention(q, k, v, interpret=False,
                                   causal=mode == "causal")
    _, text = _compile(fn, *qkv)
    if mode == "grad-pallas":   # forward + the fused dq and dkv kernels
        assert text.count(KERNEL) >= 3


@pytest.mark.parametrize("block_len", [8, 16])
@pytest.mark.parametrize("window", [1, 4, 256],
                         ids=["decode", "window4", "prefill256"])
def test_paged_attention(one_chip, block_len, window):
    """``bench_gen`` width (8 heads of 64), 512 positions per slot, a
    pool of 4096 blocks; ``window`` 1 is ``paged_attention``, 4 the
    speculative-verify ``paged_window_attention``, 256 the widest
    prefill window ``chip_smoke.py`` asks for."""
    import jax.numpy as jnp

    from mmlspark_tpu.dl.pallas_paged_attention import (
        paged_attention, paged_window_attention)
    S, H, hd, blocks = 8, 8, 64, 4096
    pool = _sds((blocks, block_len, H * hd), jnp.bfloat16, one_chip)
    rows = _sds((S, 512 // block_len), jnp.int32, one_chip)
    pos = _sds((S,), jnp.int32, one_chip)
    if window == 1:
        q = _sds((S, H, hd), jnp.bfloat16, one_chip)
        fn = paged_attention
    else:
        q = _sds((S, H, window, hd), jnp.bfloat16, one_chip)
        fn = paged_window_attention
    _compile(lambda q, k, v, r, p: fn(q, k, v, r, p, impl="pallas",
                                      interpret=False),
             q, pool, pool, rows, pos)


@pytest.mark.parametrize("H,hd,dtype,block_len", [
    (8, 64, "bfloat16", 16), (2, 16, "float32", 128),
    (32, 128, "bfloat16", 16), (16, 256, "float32", 32)],
    ids=["bench_gen", "toy-f32", "32x128", "16x256-f32"])
def test_widest_prefill_window(one_chip, H, hd, dtype, block_len):
    """A window is held whole in fast memory (at 4096 rows the chip's
    compiler refuses the kernel even for 2 heads of 16), so prefill
    chunks a long suffix at ``max_window``: that width compiles."""
    import jax.numpy as jnp

    from mmlspark_tpu.dl.pallas_paged_attention import (
        max_window, paged_window_attention)
    dtype = jnp.dtype(dtype)
    w = max_window(H, hd, dtype)
    assert w >= 64
    pool = _sds((1024, block_len, H * hd), dtype, one_chip)
    _compile(lambda q, k, v, r, p: paged_window_attention(
        q, k, v, r, p, impl="pallas", interpret=False),
        _sds((4, H, w, hd), dtype, one_chip), pool, pool,
        _sds((4, 4096 // block_len), jnp.int32, one_chip),
        _sds((4,), jnp.int32, one_chip))


#: free HBM the chip's allocator reported to the first engine built on
#: it (bytes_limit 16.91 GB less 0.24 GB of weights; my chip run, PR 23)
_FREE_HBM = 16_670_000_000


@pytest.mark.parametrize("config", ["bench_gen", "toy-serving",
                                    "toy-serving-spec", "toy-decode"])
def test_llm_engine_programs(one_chip, steer_tpu, config):
    """The engine's decode program and its widest prefill programs (the
    one that emits a token and the one with no head), pools donated and
    sized as the engine sizes them on the chip: half of
    free HBM at ``pool_block_bytes``. ``bench_gen`` is ``chip_smoke.py``'s
    engine; the toys (1 layer, 2 heads of 16, float32) are ``bench.py``'s
    two scenarios, whose narrow blocks XLA pads 8x around the kernel —
    each program's arguments and temporaries must stay inside the half
    the pools were given."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.dl import MaskedLMModel, TextEncoder
    from mmlspark_tpu.dl.paged_kv import pool_block_bytes
    from mmlspark_tpu.dl.text_encoder import make_attention_fn
    from mmlspark_tpu.obs.metrics import MetricsRegistry
    from mmlspark_tpu.serving.llm import LLMEngine

    attn = make_attention_fn("dense", causal=True)
    if config == "bench_gen":
        enc = TextEncoder(vocab=32768, width=512, depth=8, heads=8,
                          mlp_dim=2048, attention_fn=attn)
        kw, longest = dict(slots=8, block_len=16, max_seq_len=512,
                           prefill_batch=4), 256
    else:
        enc = TextEncoder(vocab=64, width=32, depth=1, heads=2,
                          mlp_dim=64, dtype=jnp.float32,
                          attention_fn=attn)
        kw, longest = {
            "toy-serving": (dict(slots=2, block_len=4, max_seq_len=22),
                            16),
            "toy-serving-spec": (dict(slots=2, block_len=4, spec_k=2,
                                      max_seq_len=22), 16),
            "toy-decode": (dict(slots=1, block_len=128,
                                max_seq_len=4096), 4064)}[config]
    module = MaskedLMModel(enc)
    spec = bool(kw.get("spec_k"))
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32)))
    params = jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, one_chip), shapes["params"])
    # the engine only closes over the module; weights and pools are
    # arguments, so tiny stand-ins build the same programs
    engine = LLMEngine(module, {"params": None},
                       draft_module=module if spec else None,
                       draft_variables={"params": None} if spec else None,
                       num_blocks=4, registry=MetricsRegistry(),
                       service="chip-compile", **kw)
    budget = _FREE_HBM // 2
    num_blocks = budget // (
        pool_block_bytes(module.cache_spec(), engine.block_len)
        * (2 if spec else 1))
    pools = jax.tree.map(
        lambda a: _sds((num_blocks,) + a.shape[1:], a.dtype, one_chip),
        engine.pools.target)
    draft = (params, pools) if spec else (None, None)
    w = max(engine.prefiller.windows_for(longest))
    programs = _lower_engine_programs(engine, w, one_chip, params,
                                      draft[0], pools, draft[1])
    at_rest = sum(np.prod(a.shape) * a.dtype.itemsize
                  for a in jax.tree.leaves(pools)) * (2 if spec else 1)
    for name, lowered in programs.items():
        compiled = lowered.compile()
        # with no head nothing reads the last block's attention: the
        # compiler drops that kernel with the block's feed-forward
        # (a riding window's kernel call beside the decode rows')
        kernels = enc.depth * (1 + name.startswith("step")) \
            - name.endswith("head0")
        assert compiled.as_text().count(KERNEL) >= kernels, name
        mem = compiled.memory_analysis()
        # donated: the pools come back in the buffers they arrived in
        assert mem.alias_size_in_bytes >= at_rest, name
        held = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                + mem.output_size_in_bytes - mem.alias_size_in_bytes)
        weights = mem.argument_size_in_bytes - mem.alias_size_in_bytes
        assert held - weights <= budget, (name, held, budget)


def test_latent_moe_engine_programs(one_chip, steer_tpu):
    """The engine's decode program and its widest prefill programs (the
    one that emits a token and the one with no head) for
    the latent-attention / expert decoder at the benchmark cell's own
    sizes (``benchmark/configs/deepseek-v2.json``, 128 slots, chains of
    34 blocks of 512, a pool of 384): five latent kernels each, the pools
    donated and NOT copied (the latent's 576 numbers rest padded to 640
    lanes; unpadded, XLA keeps the pool transposed and copies 252 MB a
    layer in and out of every program), weights and pools inside the
    chip's memory with temporaries under a tenth of a gigabyte."""
    import sys

    import jax
    import jax.numpy as jnp

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import run
    from benchmark.references import deepseek_v2 as ref
    from mmlspark_tpu.obs.metrics import MetricsRegistry

    _, wl, cfg, params = run.load_cell("deepseek-v2.doc-qa",
                                       run.load_bench())
    driver = run._load_module("drivers", wl["driver"])

    def leaf(entry):
        return _sds(entry[1], jnp.bfloat16, one_chip)

    weights = {k: leaf(v) for k, v in ref.top_shapes(cfg).items()}
    weights["layers"] = [
        {k: leaf(v) for k, v in ref.layer_shapes(cfg, i).items()}
        for i in range(int(cfg["num_hidden_layers"]))]
    num_blocks = int(params["engine"]["num_blocks"])
    small = {**params, "engine": {**params["engine"], "num_blocks": 4}}
    engine = driver.build_engine(cfg, small, weights, MetricsRegistry())
    pools = jax.tree.map(
        lambda a: _sds((num_blocks,) + a.shape[1:], a.dtype, one_chip),
        engine.pools.target)
    S, MB, w = engine.decoder.slots, engine.max_blocks, \
        engine.prefiller.max_window
    assert (S, MB, w) == (128, 34, 192)

    programs = _lower_engine_programs(engine, w, one_chip, weights, None,
                                      pools, None)
    at_rest = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                  for a in jax.tree.leaves(pools))
    assert at_rest == num_blocks * 512 * 640 * 2 * 5
    for name, lowered in programs.items():
        compiled = lowered.compile()
        text = compiled.as_text()
        assert text.count("paged_latent") >= 5, name
        assert text.count("ragged-dot") >= 12, name
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes >= at_rest, name
        # the step with a window riding holds the decode rows' and the
        # window's temporaries together
        assert mem.temp_size_in_bytes < (
            150e6 if name.startswith("step") else 100e6), (
                name, mem.temp_size_in_bytes)
        assert mem.argument_size_in_bytes < 11.7e9, name


@pytest.mark.parametrize("block_len", [128, 256, 512])
@pytest.mark.parametrize("kernel", ["sparse-decode", "sparse-prefill256",
                                    "select-decode", "select-prefill256"])
def test_block_sparse_attention_kernels(one_chip, kernel, block_len):
    """MiniCPM-SALA's widths (16 query heads a key head, 2 key heads of
    128, blocks of 64 chosen out of chains of 66,560 tokens, a pool of
    385k tokens): the sparse kernel over lists of 128 entries (its scalar
    prefetch holds a list a (token, key head)) and the scoring pass over
    the compressed-key pool, for the decode step's 128 tokens and a
    prefill window's 256."""
    import jax.numpy as jnp

    from mmlspark_tpu.dl.pallas_paged_attention import (
        select_scores, sparse_block_attention)
    G, R, hd, bs, K = 2, 16, 128, 64, 128
    NB, MB, rpb = 385024 // block_len, -(-66560 // block_len), \
        block_len // 16
    bf16 = jnp.bfloat16
    if kernel.startswith("sparse"):
        T = 128 if kernel.endswith("decode") else 256
        _compile(lambda q, kv, ph, lg, qp: sparse_block_attention(
            q, kv, ph, lg, qp, block_size=bs, scale=hd ** -0.5,
            impl="pallas", interpret=False),
            _sds((T, G, R, hd), bf16, one_chip),
            _sds((NB, block_len, G * 2 * hd), bf16, one_chip),
            _sds((T, G, K), jnp.int32, one_chip),
            _sds((T, G, K), jnp.int32, one_chip),
            _sds((T,), jnp.int32, one_chip))
    else:
        S, M = (128, R) if kernel.endswith("decode") else (1, 256 * R)
        _compile(lambda q, ck, rows: select_scores(
            q, ck, rows, scale=hd ** -0.5, impl="pallas", interpret=False),
            _sds((S, G, M, hd), bf16, one_chip),
            _sds((NB, rpb, G * hd), bf16, one_chip),
            _sds((S, MB), jnp.int32, one_chip))


@pytest.mark.parametrize("window", [1, 8, 32, 192, 256, 512],
                         ids=lambda w: f"w{w}")
def test_lightning_attention_kernels(one_chip, window):
    """32 heads of 128 over a pool of 135 states: the decode step (128
    slots, ``w`` = 1) and the chunked form of a prefill window; the pool
    comes back in the buffer it arrived in."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.dl.pallas_lightning import lightning_attention
    S, H, hd, rows = (128 if window == 1 else 1), 32, 128, 135
    qkv = [_sds((S, window, H, hd), jnp.bfloat16, one_chip)] * 3
    compiled = jax.jit(
        lambda q, k, v, st, sr, ps, ln, sl: lightning_attention(
            q, k, v, st, sr, ps, ln, sl, impl="pallas", interpret=False),
        donate_argnums=(3,)).lower(
            *qkv, _sds((rows, H, hd, hd), jnp.float32, one_chip),
            *[_sds((S,), jnp.int32, one_chip)] * 3,
            _sds((H,), jnp.float32, one_chip)).compile()
    assert KERNEL in compiled.as_text()
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= rows * H * hd * hd * 4


def test_sparse_linear_engine_programs(one_chip, steer_tpu):
    """The engine's decode program and its widest prefill programs for
    the block-sparse / lightning decoder at the benchmark cell's own sizes
    (``benchmark/configs/minicpm-sala.json``: 12 layers, 128 slots, a pool
    of 385k tokens and 135 state rows): every pool of the three kinds
    donated and not copied, the four kernels there, weights and pools
    inside the chip's memory with the temporaries of a prefill window
    (its scores against every compressed key) under half a gigabyte."""
    import sys

    import jax
    import jax.numpy as jnp

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import run
    from benchmark.references import minicpm_sala as ref
    from mmlspark_tpu.obs.metrics import MetricsRegistry

    _, wl, cfg, params = run.load_cell("minicpm-sala.long-doc-qa",
                                       run.load_bench())
    driver = run._load_module("drivers", wl["driver"])

    def leaf(entry):
        return _sds(entry[1], jnp.bfloat16, one_chip)

    weights = {k: leaf(v) for k, v in ref.top_shapes(cfg).items()}
    weights["layers"] = [
        {k: leaf(v) for k, v in ref.layer_shapes(cfg, i).items()}
        for i in range(int(cfg["num_hidden_layers"]))]
    eng = params["engine"]
    num_blocks, rows = int(eng["num_blocks"]), int(eng["state_slots"]) + 1
    small = {**params, "engine": {**eng, "num_blocks": 4, "state_slots": 2}}
    engine = driver.build_engine(cfg, small, weights, MetricsRegistry())
    pools = jax.tree.map(
        lambda a: _sds(((num_blocks if a.ndim == 3 else rows),)
                       + a.shape[1:], a.dtype, one_chip),
        engine.pools.target)
    S, MB, P = engine.decoder.slots, engine.max_blocks, \
        engine.prefiller.batch
    w = engine.prefiller.max_window
    assert (S, P, w) == (128, 1, int(params["prefill_chunk"]))
    assert MB * int(eng["block_len"]) == 66560

    programs = _lower_engine_programs(engine, w, one_chip, weights, None,
                                      pools, None)
    at_rest = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                  for a in jax.tree.leaves(pools))
    assert at_rest == 3 * num_blocks * int(eng["block_len"]) * 256 * 2 \
        * (2 + 1 / 16) + 9 * rows * 32 * 128 * 128 * 4
    for name, lowered in programs.items():
        compiled = lowered.compile()
        text = compiled.as_text()
        steps = {"decode": ("lightning_step",),
                 "step": ("lightning_step", "lightning_chunk")}.get(
                     name.split("_")[0], ("lightning_chunk",))
        for kernel in ("paged_sparse_attn", "paged_sparse_select") + steps:
            assert kernel in text, (name, kernel)
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes >= at_rest, name
        assert mem.temp_size_in_bytes < 500e6, (name,
                                                mem.temp_size_in_bytes)
        assert mem.argument_size_in_bytes < 11.7e9, name


@pytest.mark.parametrize("window", [1, 32, 512], ids=lambda w: f"w{w}")
def test_gated_delta_kernels(one_chip, window):
    """32 value heads of 128 x 128 over a pool of 137 states: the decode
    step (128 slots, ``w`` = 1) and the chunked form of a prefill window;
    the pool comes back in the buffer it arrived in."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.dl.pallas_gated_delta import gated_delta_rule
    S, H, hd, rows = (128 if window == 1 else 1), 32, 128, 137
    qkv = [_sds((S, window, H, hd), jnp.bfloat16, one_chip)] * 3
    gates = [_sds((S, window, H), jnp.float32, one_chip)] * 2
    compiled = jax.jit(
        lambda q, k, v, g, b, st, sr, ps, ln: gated_delta_rule(
            q, k, v, g, b, st, sr, ps, ln, impl="pallas", interpret=False),
        donate_argnums=(5,)).lower(
            *qkv, *gates, _sds((rows, H, hd, hd), jnp.float32, one_chip),
            *[_sds((S,), jnp.int32, one_chip)] * 3).compile()
    text = compiled.as_text()
    assert ("gated_delta_step" if window == 1 else "gated_delta_chunk") \
        in text
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= rows * H * hd * hd * 4


@pytest.mark.parametrize("block_len", [128, 256, 512])
@pytest.mark.parametrize("window", [1, 96, 512], ids=lambda w: f"w{w}")
def test_grouped_query_paged_attention(one_chip, block_len, window):
    """16 query heads on 2 key heads of 256 over pools ``[blocks,
    block_len, 512]`` holding 458k tokens, chains of 10,240: the decode
    step's 128 slots (every head in one product a cell), a riding window
    of 96 rows and one of 512 (four sub-windows of 128: a key head's 1,024
    query rows one product)."""
    import jax.numpy as jnp

    from mmlspark_tpu.dl.pallas_paged_attention import (
        GROUPED_KERNEL_NAME, paged_window_attention)
    H, G, hd = 16, 2, 256
    S = 128 if window == 1 else 1
    NB, MB = 458752 // block_len, 10240 // block_len
    pool = _sds((NB, block_len, G * hd), jnp.bfloat16, one_chip)
    _, text = _compile(
        lambda q, k, v, r, p: paged_window_attention(
            q, k, v, r, p, impl="pallas", interpret=False),
        _sds((S, H, window, hd), jnp.bfloat16, one_chip), pool, pool,
        _sds((S, MB), jnp.int32, one_chip), _sds((S,), jnp.int32, one_chip))
    assert GROUPED_KERNEL_NAME in text


def test_gated_delta_moe_engine_programs(one_chip, steer_tpu):
    """The engine's decode program, its widest prefill programs and the
    step with the widest window riding, for the gated DeltaNet / gated
    attention / expert decoder at the benchmark cell's own sizes
    (``benchmark/configs/qwen3-next-80b-a3b.json``: 8 layers, 128 experts
    of 512 held a layer, 128 slots): every pool of both kinds donated and
    not copied — a DeltaNet layer's state AND its tail — the three
    kernels there, weights and pools inside the chip's memory."""
    import sys

    import jax
    import jax.numpy as jnp

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import run
    from benchmark.references import qwen3_next as ref
    from mmlspark_tpu.obs.metrics import MetricsRegistry

    _, wl, cfg, params = run.load_cell("qwen3-next-80b-a3b.chat",
                                       run.load_bench())
    driver = run._load_module("drivers", wl["driver"])

    def leaf(entry):
        return _sds(entry[1], jnp.bfloat16, one_chip)

    weights = {k: leaf(v) for k, v in ref.top_shapes(cfg).items()}
    weights["layers"] = [
        {k: leaf(v) for k, v in ref.layer_shapes(cfg, i).items()}
        for i in range(int(cfg["num_hidden_layers"]))]
    eng = params["engine"]
    num_blocks, rows = int(eng["num_blocks"]), int(eng["state_slots"]) + 1
    small = {**params, "engine": {**eng, "num_blocks": 4, "state_slots": 2}}
    engine = driver.build_engine(cfg, small, weights, MetricsRegistry())
    spec = engine.module.cache_spec()
    pools = tuple(
        tuple(_sds(((rows if len(e) > 2 else num_blocks),) + a.shape[1:],
                   a.dtype, one_chip) for e, a in zip(layer, arrays))
        for layer, arrays in zip(spec, engine.pools.target))
    S, P = engine.decoder.slots, engine.prefiller.batch
    w = engine.prefiller.max_window
    assert (S, P, w) == (128, int(eng["prefill_batch"]),
                         int(params["prefill_chunk"]))
    assert engine.max_blocks * int(eng["block_len"]) == 10240

    programs = _lower_engine_programs(engine, w, one_chip, weights, None,
                                      pools, None)
    at_rest = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                  for a in jax.tree.leaves(pools))
    assert at_rest == 2 * num_blocks * int(eng["block_len"]) * 2048 \
        + 6 * rows * (32 * 128 * 128 * 4 + 3 * 8192 * 2)
    for name, lowered in programs.items():
        compiled = lowered.compile()
        text = compiled.as_text()
        steps = {"decode": ("gated_delta_step",),
                 "step": ("gated_delta_step", "gated_delta_chunk")}.get(
                     name.split("_")[0], ("gated_delta_chunk",))
        for kernel in ("paged_gqa_attn",) + steps:
            assert kernel in text, (name, kernel)
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes >= at_rest, name
        assert mem.temp_size_in_bytes < 1.2e9, (name,
                                                mem.temp_size_in_bytes)
        assert mem.argument_size_in_bytes < 11.5e9, name


@pytest.fixture(scope="module")
def xglm_programs(one_chip):
    """The decode program and both 192-row prefill programs of the
    benchmark's XGLM-1.7B engine (``benchmark/configs/xglm-1.7b.json``:
    24 blocks, 16 heads of 128, a 256,008-row head; 32 slots, chains of
    14 blocks of 128, a pool of 320; one prompt a prefill call) compiled
    once for the described chip: name -> (text, memory analysis), with
    the sizes the tests compare against."""
    import sys

    import jax
    import jax.numpy as jnp

    import mmlspark_tpu.dl.pallas_paged_attention as paged
    import mmlspark_tpu.utils.platform as plat

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import run
    from mmlspark_tpu.obs.metrics import MetricsRegistry

    _, wl, cfg, params = run.load_cell("xglm-1.7b.generate",
                                       run.load_bench())
    driver = run._load_module("drivers", wl["driver"])
    num_blocks = int(params["engine"]["num_blocks"])
    small = {**params, "engine": {**params["engine"], "num_blocks": 4}}
    engine = driver.build_engine(cfg, small, {"params": None},
                                 MetricsRegistry())
    dtype = jnp.dtype(cfg["cache_dtype"])
    shapes = jax.eval_shape(lambda: engine.module.init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32)))
    weights = jax.tree.map(lambda a: _sds(a.shape, dtype, one_chip),
                           shapes["params"])
    pools = jax.tree.map(
        lambda a: _sds((num_blocks,) + a.shape[1:], a.dtype, one_chip),
        engine.pools.target)
    S, MB, P, w = engine.decoder.slots, engine.max_blocks, \
        engine.prefiller.batch, engine.prefiller.max_window
    assert (S, MB, P, w) == (32, 14, 1, 192)

    # the module-scoped twin of ``steer_tpu``: the chip's path is lowered
    with pytest.MonkeyPatch.context() as m:
        m.setattr(plat, "target_platform", lambda: "tpu")
        m.setattr(paged, "target_platform", lambda: "tpu")
        lowered = _lower_engine_programs(engine, w, one_chip, weights,
                                         None, pools, None)
        compiled = {k.replace(f"_w{w}", ""): v.compile()
                    for k, v in lowered.items()}
    vocab = int(cfg["vocab_size"])
    leaves = jax.tree.leaves(pools)
    return {
        "programs": {k: (c.as_text(), c.memory_analysis())
                     for k, c in compiled.items()},
        "window_of_logits": w * vocab * 4,
        "head_bytes": int(cfg["d_model"]) * vocab * dtype.itemsize,
        "pool_shape": leaves[0].shape,
        "pool_bytes": int(np.prod(leaves[0].shape)) * dtype.itemsize,
        "at_rest": sum(int(np.prod(a.shape)) * a.dtype.itemsize
                       for a in leaves)}


def test_xglm_prefill_programs_hold_no_window_of_logits(xglm_programs):
    """The widest prefill programs: the one that emits computes the head
    for ONE row, so its temporaries stay far under the 197 MB that
    ``[192, 256008]`` float32 logits take (with the head over every row
    they were 226 MB), and the one with no head does not even take the
    head's matrix."""
    mem = {head: xglm_programs["programs"][f"prefill_head{head:d}"][1]
           for head in (True, False)}
    assert mem[True].temp_size_in_bytes \
        < xglm_programs["window_of_logits"] // 2
    assert mem[False].temp_size_in_bytes <= mem[True].temp_size_in_bytes
    assert mem[False].argument_size_in_bytes \
        <= mem[True].argument_size_in_bytes - xglm_programs["head_bytes"]


@pytest.mark.parametrize("name,kernels", [
    ("decode", 24), ("prefill_head1", 24), ("prefill_head0", 23),
    ("step", 48)])
def test_xglm_programs_keep_the_lane_dense_pools_in_place(
        xglm_programs, name, kernels):
    """The cache rests ``[320, 128, 2048]`` bfloat16, a token's 16 heads
    of 128 side by side, which is the layout the paged kernel reads a
    block in: every program takes the 48 pools and hands them back in the
    buffers they came in, holds no temporary of a pool's size (a pool
    re-laid out around the scatter or the kernel would be one), and runs
    one Mosaic kernel a layer (with no head nothing reads the last
    block's attention: the compiler drops that kernel with the block's
    feed-forward)."""
    text, mem = xglm_programs["programs"][name]
    assert xglm_programs["pool_shape"] == (320, 128, 2048)
    assert xglm_programs["at_rest"] == 48 * xglm_programs["pool_bytes"]
    assert text.count(KERNEL) == kernels
    assert mem.alias_size_in_bytes >= xglm_programs["at_rest"]
    assert mem.temp_size_in_bytes < xglm_programs["pool_bytes"]
    # no copy and no transpose makes an array of a pool's shape (as a
    # pool rests, or flat as the scatter sees it)
    for line in text.splitlines():
        if " copy(" in line or " transpose(" in line:
            made = line.split("=")[1].split("(")[0]
            assert "bf16[320,128,2048]" not in made \
                and "bf16[40960,2048]" not in made, line


def test_gbdt_boosting_step(one_chip, steer_tpu, monkeypatch):
    """The fused boosting step (gradients, growth with the histogram
    kernel under ``lax.cond``, score update) for 500k x 28."""
    import jax

    import mmlspark_tpu.lightgbm.pallas_hist as pallas_hist
    import mmlspark_tpu.lightgbm.trainer as trainer
    from mmlspark_tpu.core import DataFrame
    from mmlspark_tpu.lightgbm import LightGBMClassifier

    # a tiny CPU fit (scatter path) hands over the step's statics and
    # the pytree of its arguments
    captured = {}
    build = trainer._fused_cached

    def spy(st):
        step, chunk = build(st)

        def wrapped(*args):
            captured.setdefault("step", (st, args))
            return step(*args)
        return wrapped, chunk

    n0 = 512
    rng = np.random.default_rng(7)
    feats = rng.normal(size=(n0, 28)).astype(np.float32)
    labels = (feats[:, :4].sum(1) > 0).astype(np.float32)
    with monkeypatch.context() as m:
        m.setattr(trainer, "_fused_cached", spy)
        m.setattr(pallas_hist, "use_pallas_hist", lambda: False)
        LightGBMClassifier(numIterations=2, numLeaves=31, numShards=1,
                           learningRate=0.1).fit(
            DataFrame({"features": feats, "label": labels}))
    st, args = captured["step"]
    assert st.n == n0 and pallas_hist.use_pallas_hist()   # steered: tpu

    n = 500_000
    args = jax.tree.map(
        lambda a: _sds([n if d == n0 else d for d in np.shape(a)],
                       a.dtype, one_chip), args)
    step, _ = trainer._build_fused(st._replace(n=n))
    assert KERNEL in step.lower(*args).compile().as_text()
