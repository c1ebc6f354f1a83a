"""Pipeline parallelism (pp) and expert parallelism (ep) against
single-device references."""

import pytest

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from mmlspark_tpu.models.moe import (init_moe_params, make_sharded_moe,
                                     moe_forward)
from mmlspark_tpu.parallel.pipeline import (make_pipeline_mlp,
                                            pipeline_apply,
                                            pipeline_train_1f1b)


def pp_mesh(n=4):
    return Mesh(np.asarray(jax.devices()[:n]), ("pp",))


@pytest.mark.slow
class TestPipelineParallel:
    def test_matches_sequential(self):
        S, M, mb, width = 4, 6, 2, 8
        rng = np.random.default_rng(0)
        Ws = rng.normal(scale=0.3, size=(S, width, width)) \
            .astype(np.float32)
        bs = rng.normal(scale=0.1, size=(S, width)).astype(np.float32)
        x = rng.normal(size=(M, mb, width)).astype(np.float32)

        stage_fn = make_pipeline_mlp(width)
        out = pipeline_apply(pp_mesh(S), stage_fn,
                             (jnp.asarray(Ws), jnp.asarray(bs)),
                             jnp.asarray(x))

        # sequential reference: stages applied in order to each microbatch
        ref = x.copy()
        for s in range(S):
            for m in range(M):
                ref[m] = np.asarray(stage_fn((Ws[s], bs[s]),
                                             jnp.asarray(ref[m])))
        np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5)

    def test_two_stage(self):
        S, M, mb, width = 2, 3, 4, 8
        rng = np.random.default_rng(1)
        Ws = rng.normal(scale=0.3, size=(S, width, width)) \
            .astype(np.float32)
        bs = np.zeros((S, width), np.float32)
        x = rng.normal(size=(M, mb, width)).astype(np.float32)
        stage_fn = make_pipeline_mlp(width)
        out = pipeline_apply(pp_mesh(S), stage_fn,
                             (jnp.asarray(Ws), jnp.asarray(bs)),
                             jnp.asarray(x))
        ref = x.copy()
        for s in range(S):
            for m in range(M):
                ref[m] = np.asarray(stage_fn((Ws[s], bs[s]),
                                             jnp.asarray(ref[m])))
        np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5)


@pytest.mark.slow
class TestPipeline1F1B:
    """The interleaved schedule must produce the SAME loss and param
    grads as a dense (single-device, sequential) fwd+bwd."""

    def _dense(self, stage_fn, loss_fn, Ws, bs, x, y, S, M):
        def total(params):
            Ws, bs = params
            acc = 0.0
            for m in range(M):
                h = x[m]
                for s in range(S):
                    h = stage_fn((Ws[s], bs[s]), h)
                acc = acc + loss_fn(h, y[m])
            return acc / M
        return jax.value_and_grad(total)((Ws, bs))

    def _check(self, S, M, mb=2, width=8, seed=0):
        rng = np.random.default_rng(seed)
        Ws = jnp.asarray(rng.normal(scale=0.3, size=(S, width, width)),
                         jnp.float32)
        bs = jnp.asarray(rng.normal(scale=0.1, size=(S, width)),
                         jnp.float32)
        x = jnp.asarray(rng.normal(size=(M, mb, width)), jnp.float32)
        y = jnp.asarray(rng.normal(size=(M, mb, width)), jnp.float32)
        stage_fn = make_pipeline_mlp(width)

        def loss_fn(h, t):
            return jnp.mean((h - t) ** 2)

        loss, grads = pipeline_train_1f1b(
            pp_mesh(S), stage_fn, loss_fn, (Ws, bs), x, y)
        ref_loss, ref_grads = self._dense(stage_fn, loss_fn, Ws, bs,
                                          x, y, S, M)
        np.testing.assert_allclose(float(loss), float(ref_loss),
                                   rtol=1e-5)
        for g, r in zip(jax.tree.leaves(grads),
                        jax.tree.leaves(ref_grads)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       atol=2e-5)

    def test_matches_dense_4stage(self):
        self._check(S=4, M=6)

    def test_matches_dense_2stage(self):
        self._check(S=2, M=3, seed=1)

    def test_single_stage_degenerate(self):
        self._check(S=1, M=4, seed=2)

    def test_memory_ring_wraps(self):
        # M >> S exercises ring-slot reuse (K = 2S slots, M=12 writes)
        self._check(S=2, M=12, seed=3)

    def test_real_encoder_full_param_grads(self):
        """pipeline_train_encoder_1f1b trains the WHOLE TextEncoder —
        embedding prologue, every block, LN epilogue — with loss and
        grads equal to the dense single-device jax.grad."""
        from mmlspark_tpu.dl.text_encoder import TextEncoder
        from mmlspark_tpu.parallel.pipeline import (
            pipeline_train_encoder_1f1b)

        S = 4
        rng = np.random.default_rng(7)
        enc = TextEncoder(vocab=64, width=16, depth=S, heads=2,
                          mlp_dim=32, dtype=jnp.float32)
        ids = rng.integers(1, 64, size=(8, 10)).astype(np.int32)
        ids[:, 8:] = 0                    # pad tail: real key masks
        variables = enc.init(jax.random.PRNGKey(0), jnp.asarray(ids))
        y = jnp.asarray(rng.normal(size=(8,)), jnp.float32)

        def loss_on_pooled(pooled, y_mb):
            return jnp.mean((pooled.mean(-1) - y_mb) ** 2)

        loss, grads = pipeline_train_encoder_1f1b(
            pp_mesh(S), enc, variables, jnp.asarray(ids), y,
            loss_on_pooled)

        def dense(params):
            out = enc.apply({"params": params}, jnp.asarray(ids))
            return jnp.mean((out["pooled"].mean(-1) - y) ** 2)

        ref_loss, ref_grads = jax.value_and_grad(dense)(
            variables["params"])
        # microbatching changes the loss DEFINITION (mean of per-mb
        # means == overall mean only for equal mb sizes — true here)
        np.testing.assert_allclose(float(loss), float(ref_loss),
                                   rtol=1e-5)
        flat_g = dict(jax.tree_util.tree_flatten_with_path(grads)[0])
        flat_r = dict(jax.tree_util.tree_flatten_with_path(
            ref_grads)[0])
        assert flat_g.keys() == flat_r.keys()
        for k in flat_r:
            np.testing.assert_allclose(
                np.asarray(flat_g[k]), np.asarray(flat_r[k]),
                atol=5e-5, err_msg=str(k))


@pytest.mark.slow
class TestExpertParallel:
    def test_sharded_matches_single_device(self):
        E, D, H, T = 8, 16, 32, 24
        params = init_moe_params(jax.random.PRNGKey(0), E, D, H)
        x = jax.random.normal(jax.random.PRNGKey(1), (T, D))
        ref = moe_forward(params, x)

        mesh = Mesh(np.asarray(jax.devices()), ("ep",))
        sharded = make_sharded_moe(mesh)
        out = sharded(params, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)

    def test_routing_uses_all_experts(self):
        E, D, H, T = 8, 16, 8, 256
        params = init_moe_params(jax.random.PRNGKey(2), E, D, H)
        x = jax.random.normal(jax.random.PRNGKey(3), (T, D))
        logits = x @ params["router"]
        used = set(np.asarray(jnp.argmax(logits, axis=-1)).tolist())
        assert len(used) >= E // 2  # router spreads tokens


class TestCapacityDispatch:
    """Scalable O(T·capacity) dispatch (review round 4 Weak #6: the dense
    one-hot einsum runs every token through every local expert —
    compute ×E/n with expert count)."""

    def _setup(self, E=8, D=16, H=32, T=64, seed=0):
        params = init_moe_params(jax.random.PRNGKey(seed), E, D, H)
        x = jax.random.normal(jax.random.PRNGKey(seed + 1), (T, D))
        return params, x

    def test_high_capacity_equals_dense_oracle(self):
        """cf ≥ E → no token can overflow → capacity dispatch must
        reproduce the dense-masked formulation exactly."""
        params, x = self._setup()
        dense = moe_forward(params, x)
        cap = moe_forward(params, x, capacity_factor=8.0)
        np.testing.assert_allclose(np.asarray(cap), np.asarray(dense),
                                   atol=1e-5)

    def test_overflow_drops_in_queue_order(self):
        """Collapse routing onto expert 0: only the first C tokens get
        an expert contribution (Switch first-come-first-served), the
        rest output exactly zero (residual untouched)."""
        E, D, H, T = 8, 16, 32, 64
        params, x = self._setup(E=E, D=D, H=H, T=T)
        x = jnp.abs(x) + 0.1   # positive features: the all-ones router
        params = dict(params)  # column below then wins for EVERY token
        params["router"] = jnp.zeros((D, E)).at[:, 0].set(
            10 * jnp.ones(D))
        cf = 2.0
        C = int(np.ceil(T / E * cf))
        out = np.asarray(moe_forward(params, x, capacity_factor=cf))
        dense = np.asarray(moe_forward(params, x))
        np.testing.assert_allclose(out[:C], dense[:C], atol=1e-5)
        np.testing.assert_array_equal(out[C:], 0.0)
        assert np.abs(dense[C:]).max() > 0  # dense DID compute them

    @pytest.mark.slow
    def test_sharded_capacity_matches_single(self):
        """Shards rank queues from the same all-gathered routing, so
        drops agree with the single-device capacity path exactly."""
        params, x = self._setup(T=48)
        mesh = Mesh(np.asarray(jax.devices()), ("ep",))
        single = moe_forward(params, x, capacity_factor=1.25)
        sharded = make_sharded_moe(mesh, capacity_factor=1.25)(params, x)
        np.testing.assert_allclose(np.asarray(sharded),
                                   np.asarray(single), atol=1e-5)

    @pytest.mark.slow
    def test_dispatch_flops_independent_of_expert_count(self):
        """The point of the formulation: quadrupling E leaves capacity
        compute ~flat (dense grows ~4x). Asserted with XLA's own cost
        analysis."""
        D, H, T, cf = 32, 64, 256, 1.0

        def flops(E, capacity_factor):
            params = init_moe_params(jax.random.PRNGKey(0), E, D, H)
            x = jnp.ones((T, D))
            f = jax.jit(lambda p, x: moe_forward(
                p, x, capacity_factor=capacity_factor))
            cost = f.lower(params, x).compile().cost_analysis()
            if isinstance(cost, (list, tuple)):  # old-JAX shape
                cost = cost[0]
            return float(cost["flops"])

        dense_ratio = flops(32, None) / flops(8, None)
        cap_ratio = flops(32, cf) / flops(8, cf)
        assert dense_ratio > 3.0, dense_ratio      # dense scales with E
        assert cap_ratio < 1.5, cap_ratio          # capacity does not

    def test_pads_do_not_consume_capacity(self):
        """Pad positions embed identically, so they all route to one
        expert; ranked ahead of real tokens they would crowd them past
        C. The valid mask must keep every real token's contribution
        intact in a heavily padded batch."""
        E, D, H = 8, 16, 32
        params, x = self._setup(E=E, D=D, H=H, T=96)
        valid = jnp.zeros(96, bool).at[64:].set(True)  # pads FIRST
        dense = np.asarray(moe_forward(params, x))
        cap = np.asarray(moe_forward(params, x, capacity_factor=2.0,
                                     valid=valid))
        # capacity per expert C = ceil(96/8*2) = 24 >= real tokens per
        # expert, so with pads excluded nothing real can overflow
        np.testing.assert_allclose(cap[64:], dense[64:], atol=1e-5)
        np.testing.assert_array_equal(cap[:64], 0.0)  # pads get none
        # encoder-level wiring: the pad mask threads through
        # moe_text_encoder_forward into the dispatch — with capacity
        # high enough that nothing real overflows, a padded batch must
        # match its dense (exact) twin, which only holds if pads were
        # excluded from ranking (they'd otherwise overflow expert
        # queues at this cf on their own)
        from mmlspark_tpu.models.moe import (init_moe_blocks,
                                             moe_text_encoder_forward)
        from mmlspark_tpu.dl.text_encoder import TextEncoder
        import functools
        enc = TextEncoder(vocab=64, width=16, depth=1, heads=2,
                          mlp_dim=32, dtype=jnp.float32)
        rng = np.random.default_rng(5)
        padded = np.zeros((4, 32), np.int32)
        padded[:, :4] = rng.integers(1, 64, size=(4, 4))
        enc_vars = enc.init(jax.random.PRNGKey(0), jnp.asarray(padded))
        blocks = init_moe_blocks(jax.random.PRNGKey(1), 1, 16, 8, 32)
        # 16 real tokens over 8 experts, C = ceil(128/8*1.0) = 16: no
        # real token can overflow, but the 112 pads would fill every
        # queue if counted
        ap = functools.partial(moe_forward, capacity_factor=1.0)
        out_cap = moe_text_encoder_forward(enc, enc_vars, blocks,
                                           jnp.asarray(padded),
                                           moe_apply=ap)
        out_dense = moe_text_encoder_forward(enc, enc_vars, blocks,
                                             jnp.asarray(padded))
        np.testing.assert_allclose(np.asarray(out_cap["pooled"]),
                                   np.asarray(out_dense["pooled"]),
                                   atol=1e-4)

    def test_capacity_is_trainable(self):
        """Gradients reach router and experts through the scatter/
        gather dispatch (the Switch gate multiplier path)."""
        params, x = self._setup()

        def loss(p):
            return jnp.sum(moe_forward(p, x, capacity_factor=1.25) ** 2)

        g = jax.grad(loss)(params)
        assert float(jnp.abs(g["router"]).max()) > 0
        assert float(jnp.abs(g["w_in"]).max()) > 0
        assert float(jnp.abs(g["w_out"]).max()) > 0

    @pytest.mark.slow
    def test_train_step_capacity_default(self):
        """make_moe_train_step defaults to capacity dispatch and still
        trains the real MoE encoder."""
        import optax

        from mmlspark_tpu.dl.text_encoder import TextEncoder
        from mmlspark_tpu.models.moe import (init_moe_blocks,
                                             make_moe_train_step)
        rng = np.random.default_rng(0)
        enc = TextEncoder(vocab=64, width=16, depth=2, heads=2,
                          mlp_dim=32, dtype=jnp.float32)
        ids = jnp.asarray(rng.integers(1, 64, size=(8, 12)), jnp.int32)
        y = jnp.asarray(rng.integers(0, 2, size=8), jnp.float32)
        enc_vars = enc.init(jax.random.PRNGKey(0), ids)
        mesh = Mesh(np.asarray(jax.devices()), ("ep",))
        blocks = init_moe_blocks(jax.random.PRNGKey(1), enc.depth, 16,
                                 8, 32)
        tx = optax.sgd(1e-2)
        step = make_moe_train_step(mesh, enc, tx)   # cf=1.25 default
        opt = tx.init((enc_vars, blocks))
        losses = []
        for _ in range(8):
            opt, enc_vars, blocks, task, balance = step(
                opt, enc_vars, blocks, ids, y)
            losses.append(float(task))
            assert np.isfinite(losses[-1]) and np.isfinite(
                float(balance))
        assert losses[-1] < losses[0]


@pytest.mark.slow
class TestMoETraining:
    """Trainable expert parallelism (review round 3 Weak #5: MoE was
    inference-only with no load-balancing loss)."""

    def test_balance_loss_uniform_and_collapsed(self):
        from mmlspark_tpu.models.moe import load_balance_loss
        E, T = 8, 512
        # near-uniform routing → loss ≈ 1.0 (the Switch normalization)
        logits = jax.random.normal(jax.random.PRNGKey(0), (T, E)) * 0.01
        expert = jnp.argmax(logits, axis=-1)
        near_uniform = float(load_balance_loss(logits, expert))
        assert abs(near_uniform - 1.0) < 0.1, near_uniform
        # collapsed routing (everything to expert 0) → loss → E
        logits_c = jnp.zeros((T, E)).at[:, 0].set(10.0)
        collapsed = float(load_balance_loss(
            logits_c, jnp.argmax(logits_c, axis=-1)))
        assert collapsed > 4.0, collapsed

    def test_aux_matches_sharded_and_single(self):
        from mmlspark_tpu.models.moe import make_sharded_moe
        E, D, H, T = 8, 16, 32, 64
        params = init_moe_params(jax.random.PRNGKey(4), E, D, H)
        x = jax.random.normal(jax.random.PRNGKey(5), (T, D))
        y_ref, aux_ref = moe_forward(params, x, return_aux=True)
        mesh = Mesh(np.asarray(jax.devices()), ("ep",))
        sharded = make_sharded_moe(mesh, return_aux=True)
        y_sh, aux_sh = jax.jit(sharded)(params, x)
        np.testing.assert_allclose(np.asarray(y_sh), np.asarray(y_ref),
                                   atol=1e-5)
        np.testing.assert_allclose(float(aux_sh["balance_loss"]),
                                   float(aux_ref["balance_loss"]),
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(aux_sh["expert_fraction"]),
                                   np.asarray(aux_ref["expert_fraction"]),
                                   atol=1e-6)

    def test_sharded_gradients_match_single_device(self):
        """ep joins pp/sp's equivalence bar: jax.grad through the
        shard_map forward (incl. the replicated balance-loss aux path)
        must match the single-device gradients — a transpose-path
        regression that scales cotangents by the device count would
        stay finite and keep loss decreasing, so only allclose
        catches it."""
        from mmlspark_tpu.models.moe import make_sharded_moe
        E, D, H, T = 8, 16, 32, 64
        params = init_moe_params(jax.random.PRNGKey(12), E, D, H)
        x = jax.random.normal(jax.random.PRNGKey(13), (T, D))
        mesh = Mesh(np.asarray(jax.devices()), ("ep",))
        sharded = make_sharded_moe(mesh, return_aux=True)

        def make_loss(fwd):
            def loss(p):
                y, aux = fwd(p, x)
                return (y ** 2).sum() + 1e-2 * aux["balance_loss"]
            return loss

        g_single = jax.grad(make_loss(
            lambda p, x: moe_forward(p, x, return_aux=True)))(params)
        g_sharded = jax.jit(jax.grad(make_loss(sharded)))(params)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=2e-5, rtol=1e-4),
            g_sharded, g_single)

    def test_gradients_reach_router_and_experts(self):
        E, D, H, T = 8, 16, 32, 64
        params = init_moe_params(jax.random.PRNGKey(6), E, D, H)
        x = jax.random.normal(jax.random.PRNGKey(7), (T, D))

        def loss(p):
            y, aux = moe_forward(p, x, return_aux=True)
            return (y ** 2).sum() + 1e-2 * aux["balance_loss"]

        g = jax.grad(loss)(params)
        assert float(jnp.abs(g["router"]).max()) > 0
        assert float(jnp.abs(g["w_in"]).max()) > 0
        assert float(jnp.abs(g["w_out"]).max()) > 0

    def test_moe_encoder_trains_expert_parallel(self):
        """Full sharded training step: loss decreases over steps and
        experts stay sharded through the optimizer update."""
        import optax

        from mmlspark_tpu.dl.text_encoder import TextEncoder
        from mmlspark_tpu.models.moe import (init_moe_blocks,
                                             make_moe_train_step)
        module = TextEncoder(vocab=64, width=16, depth=2, heads=2,
                             mlp_dim=32, dtype=jnp.float32)
        rng = np.random.default_rng(8)
        ids = jnp.asarray(rng.integers(1, 64, size=(8, 12)), jnp.int32)
        y = jnp.asarray(rng.integers(0, 2, size=8), jnp.float32)
        variables = module.init(jax.random.PRNGKey(9), ids)
        moe_blocks = init_moe_blocks(jax.random.PRNGKey(10),
                                     module.depth, 16, 8, 32)
        mesh = Mesh(np.asarray(jax.devices()), ("ep",))
        tx = optax.adam(3e-3)
        step = make_moe_train_step(mesh, module, tx)
        opt_state = tx.init((variables, moe_blocks))
        losses = []
        for _ in range(8):
            opt_state, variables, moe_blocks, task, balance = step(
                opt_state, variables, moe_blocks, ids, y)
            losses.append(float(task))
            assert np.isfinite(float(balance))
        assert losses[-1] < losses[0], losses


@pytest.mark.slow
class TestPipelineRealModel:
    """pipeline_encode: the REAL TextEncoder blocks as GPipe stages must
    reproduce the plain single-device forward (same blocks, same order —
    float32 everywhere so the comparison is tight)."""

    def _encoder(self, depth):
        from mmlspark_tpu.dl.text_encoder import TextEncoder
        return TextEncoder(vocab=128, width=16, depth=depth, heads=2,
                           mlp_dim=32, dtype=jnp.float32)

    def test_matches_plain_forward(self):
        from mmlspark_tpu.parallel.pipeline import pipeline_encode
        module = self._encoder(depth=8)  # 2 blocks per stage on S=4
        rng = np.random.default_rng(0)
        ids = rng.integers(1, 128, size=(8, 12)).astype(np.int32)
        ids[:, 9:] = 0  # pad tail — key masks must ride the microbatches
        ids[3, 4:] = 0
        variables = module.init(jax.random.PRNGKey(0), jnp.asarray(ids))
        plain = module.apply(variables, jnp.asarray(ids))
        piped = pipeline_encode(pp_mesh(4), module, variables,
                                jnp.asarray(ids))
        np.testing.assert_allclose(np.asarray(piped["pooled"]),
                                   np.asarray(plain["pooled"]),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(piped["tokens"]),
                                   np.asarray(plain["tokens"]),
                                   atol=1e-5, rtol=1e-5)

    def test_depth_must_divide(self):
        import pytest
        from mmlspark_tpu.parallel.pipeline import pipeline_encode
        module = self._encoder(depth=6)
        ids = jnp.ones((4, 8), jnp.int32)
        variables = module.init(jax.random.PRNGKey(0), ids)
        with pytest.raises(ValueError, match="divide"):
            pipeline_encode(pp_mesh(4), module, variables, ids)


@pytest.mark.slow
class TestPipelineTraining:
    """Gradients THROUGH the pipeline (review round 3 item 9): the tick
    schedule is a scan, so jax.grad runs the backward pipeline over the
    same ring — pp joins sp as a trainable strategy. Equivalence bar is
    the dense single-device gradient, like the ring-attention training
    test (``test_parallel.py``)."""

    def test_mlp_pipeline_gradients_match_sequential(self):
        S, M, mb, width = 4, 4, 2, 8
        rng = np.random.default_rng(3)
        Ws = rng.normal(scale=0.3, size=(S, width, width)) \
            .astype(np.float32)
        bs = rng.normal(scale=0.1, size=(S, width)).astype(np.float32)
        x = rng.normal(size=(M, mb, width)).astype(np.float32)
        stage_fn = make_pipeline_mlp(width)
        mesh = pp_mesh(S)

        def piped_loss(params):
            out = pipeline_apply(mesh, stage_fn, params, jnp.asarray(x))
            return (out ** 2).sum()

        def seq_loss(params):
            Ws, bs = params
            h = jnp.asarray(x)
            for s in range(S):
                h = jax.vmap(lambda m: stage_fn((Ws[s], bs[s]), m))(h)
            return (h ** 2).sum()

        gp = jax.grad(piped_loss)((jnp.asarray(Ws), jnp.asarray(bs)))
        gs = jax.grad(seq_loss)((jnp.asarray(Ws), jnp.asarray(bs)))
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4),
            gp, gs)

    def test_encoder_trains_through_pipeline(self):
        """Full train step with the encoder's blocks as GPipe stages:
        one optimizer update through pipeline_encode must match the
        dense update (params, loss), with and without stage remat."""
        import optax

        from mmlspark_tpu.parallel.pipeline import pipeline_encode

        from mmlspark_tpu.dl.text_encoder import TextEncoder
        module = TextEncoder(vocab=128, width=16, depth=4, heads=2,
                             mlp_dim=32, dtype=jnp.float32)
        rng = np.random.default_rng(11)
        ids = jnp.asarray(rng.integers(1, 128, size=(8, 16)), jnp.int32)
        y = jnp.asarray(rng.integers(0, 2, size=8), jnp.float32)
        variables = module.init(jax.random.PRNGKey(4), ids)
        mesh = pp_mesh(4)
        tx = optax.sgd(1e-2)

        def dense_loss(params):
            out = module.apply({"params": params}, ids)
            return jnp.mean((out["pooled"].mean(-1) - y) ** 2)

        def make_piped_loss(remat):
            def piped_loss(params):
                out = pipeline_encode(mesh, module, {"params": params},
                                      ids, remat_stage=remat)
                return jnp.mean((out["pooled"].mean(-1) - y) ** 2)
            return piped_loss

        p0 = variables["params"]
        ld, gd = jax.jit(jax.value_and_grad(dense_loss))(p0)
        for remat in (False, True):
            # jit is required: an eagerly-traced grad through shard_map
            # hits the closed_call limitation (and real training is
            # jitted anyway)
            lp, gp = jax.jit(jax.value_and_grad(
                make_piped_loss(remat)))(p0)
            np.testing.assert_allclose(float(lp), float(ld), rtol=1e-5)
            jax.tree.map(
                lambda a, b: np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), atol=2e-5, rtol=1e-4),
                gp, gd)
        # and a real optimizer step end-to-end (jitted)
        opt_state = tx.init(p0)

        @jax.jit
        def step(params, opt_state):
            loss, g = jax.value_and_grad(make_piped_loss(False))(params)
            updates, opt_state = tx.update(g, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        p1, opt_state, loss1 = step(p0, opt_state)
        p2, _, loss2 = step(p1, opt_state)
        assert float(loss2) < float(loss1)


@pytest.mark.slow
class TestMoERealModel:
    """Expert parallelism composed with the REAL TextEncoder (r2 weak
    #6: ep previously ran only a toy MLP): attention trunk replicated,
    each block's feed-forward swapped for a top-1 MoE with experts
    sharded over ep."""

    def _setup(self, depth=2, experts=8):
        from mmlspark_tpu.dl.text_encoder import TextEncoder
        from mmlspark_tpu.models.moe import init_moe_blocks
        module = TextEncoder(vocab=128, width=16, depth=depth, heads=2,
                             mlp_dim=32, dtype=jnp.float32)
        rng = np.random.default_rng(0)
        ids = rng.integers(1, 128, size=(4, 10)).astype(np.int32)
        ids[:, 8:] = 0
        variables = module.init(jax.random.PRNGKey(0), jnp.asarray(ids))
        moe_blocks = init_moe_blocks(jax.random.PRNGKey(1), depth, 16,
                                     experts, 32)
        return module, variables, moe_blocks, jnp.asarray(ids)

    def test_sharded_matches_single_device(self):
        from mmlspark_tpu.models.moe import (make_moe_text_encoder,
                                             moe_text_encoder_forward)
        module, variables, moe_blocks, ids = self._setup()
        single = moe_text_encoder_forward(module, variables, moe_blocks,
                                          ids)
        mesh = Mesh(np.asarray(jax.devices()[:8]), ("ep",))
        sharded = make_moe_text_encoder(mesh, module, variables,
                                        moe_blocks)(ids)
        np.testing.assert_allclose(np.asarray(sharded["pooled"]),
                                   np.asarray(single["pooled"]),
                                   atol=1e-5, rtol=1e-5)

    def test_moe_actually_routes(self):
        """Different tokens hit different experts (the router is live,
        not a constant path)."""
        from mmlspark_tpu.models.moe import moe_text_encoder_forward
        module, variables, moe_blocks, ids = self._setup(depth=1)
        out = moe_text_encoder_forward(module, variables, moe_blocks,
                                       ids)
        h = module.apply(variables, ids, method="embed_ids")
        logits = np.asarray(
            h.reshape(-1, 16) @ moe_blocks[0]["router"])
        assert len(set(np.argmax(logits, axis=-1).tolist())) > 1
        assert np.isfinite(np.asarray(out["pooled"])).all()
