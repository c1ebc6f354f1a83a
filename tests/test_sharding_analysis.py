"""Sharding is MEASURED, not asserted (review round 1 weak #8): inspect the
actual placements `shard_train_state` produces and the collectives XLA
inserts into the compiled dp/tp train step, ring attention, and the
distributed GBDT grower — the compiled-HLO ground truth of the SPMD
design (scaling-book recipe: annotate, compile, verify the collectives).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, PartitionSpec as P

from mmlspark_tpu.parallel.compat import shard_map
from mmlspark_tpu.dl.train import (init_train_state, make_train_step,
                                   shard_train_state)
from mmlspark_tpu.models.resnet import BasicBlock, ResNet


@pytest.fixture(scope="module")
def dp_tp_mesh():
    devices = np.asarray(jax.devices()).reshape(4, 2)
    return Mesh(devices, ("dp", "tp"))


def _hlo(compiled) -> str:
    return compiled.as_text()


class TestTrainStepCollectives:
    @pytest.fixture(scope="class")
    def compiled(self, dp_tp_mesh):
        module = ResNet(stage_sizes=(1, 1), block=BasicBlock, width=64,
                        num_classes=128, dtype=jnp.float32)
        tx = optax.sgd(1e-2)
        x = np.zeros((8, 16, 16, 3), np.float32)
        y = np.zeros(8, np.int32)
        state = init_train_state(module, jax.random.PRNGKey(0), x[:1], tx)
        state = shard_train_state(state, dp_tp_mesh)
        step = make_train_step(module, tx, mesh=dp_tp_mesh)
        lowered = jax.jit(step).lower(state, jnp.asarray(x),
                                      jnp.asarray(y))
        return state, lowered.compile()

    def test_large_kernels_are_tp_sharded(self, dp_tp_mesh):
        module = ResNet(stage_sizes=(1, 1), block=BasicBlock, width=64,
                        num_classes=128, dtype=jnp.float32)
        tx = optax.sgd(1e-2)
        x = np.zeros((1, 16, 16, 3), np.float32)
        state = init_train_state(module, jax.random.PRNGKey(0), x, tx)
        state = shard_train_state(state, dp_tp_mesh)
        specs = {}
        for path, leaf in jax.tree_util.tree_leaves_with_path(
                state.params):
            name = jax.tree_util.keystr(path)
            specs[name] = leaf.sharding.spec
        sharded = {n: s for n, s in specs.items() if "tp" in str(s)}
        # the big conv kernels and the dense head must be tp-sharded on
        # their output-channel dim; biases/norm scales replicated
        assert sharded, f"no parameter got a tp sharding: {specs}"
        assert any("head" in n or "Conv" in n for n in sharded)
        for name, spec in specs.items():
            if "scale" in name or "bias" in name:
                assert "tp" not in str(spec), (name, spec)

    def test_compiled_step_contains_gradient_allreduce(self, compiled):
        state, exe = compiled
        hlo = _hlo(exe)
        assert "all-reduce" in hlo, "no gradient all-reduce in HLO"

class TestStepExecutionKeepsShardings:
    def test_new_state_keeps_placements(self, dp_tp_mesh):
        module = ResNet(stage_sizes=(1, 1), block=BasicBlock, width=64,
                        num_classes=128, dtype=jnp.float32)
        tx = optax.sgd(1e-2)
        x = np.random.default_rng(0).normal(
            size=(8, 16, 16, 3)).astype(np.float32)
        y = (np.arange(8) % 128).astype(np.int32)
        state = init_train_state(module, jax.random.PRNGKey(0), x[:1], tx)
        state = shard_train_state(state, dp_tp_mesh)
        before = [l.sharding for l in jax.tree.leaves(state.params)]
        step = make_train_step(module, tx, mesh=dp_tp_mesh)
        new_state, loss = step(state, jnp.asarray(x), jnp.asarray(y))
        after = [l.sharding for l in jax.tree.leaves(new_state.params)]
        assert np.isfinite(float(loss))
        for b, a in zip(before, after):
            assert b.spec == a.spec, (b, a)


class TestRingAttentionCollectives:
    def test_ppermute_in_hlo(self):
        from mmlspark_tpu.parallel.ring_attention import make_ring_attention
        mesh = Mesh(np.asarray(jax.devices()), ("sp",))
        ring = make_ring_attention(mesh, causal=False)
        q = jnp.zeros((1, 2, 64, 16), jnp.float32)
        lowered = jax.jit(ring).lower(q, q, q)
        hlo = lowered.compile().as_text()
        assert "collective-permute" in hlo, (
            "ring attention must rotate kv blocks via collective-permute")


class TestGBDTCollectives:
    def test_histogram_psum_in_hlo(self):
        from mmlspark_tpu.lightgbm.engine import TreeParams, grow_tree
        mesh = Mesh(np.asarray(jax.devices()), ("dp",))
        tp = TreeParams(num_leaves=7, max_bin=15)
        F = 6

        def local(b, g, h, fm, rm):
            return grow_tree(b, g, h, fm, rm, params=tp, num_features=F,
                             psum_axis="dp")

        fn = shard_map(local, mesh=mesh,
                           in_specs=(P("dp"), P("dp"), P("dp"), P(),
                                     P("dp")),
                           out_specs=(P(), P("dp")), check_vma=False)
        bins = jnp.zeros((64, F), jnp.uint8)
        g = jnp.zeros(64, jnp.float32)
        fm = jnp.ones(F, bool)
        rm = jnp.ones(64, jnp.float32)
        hlo = jax.jit(fn).lower(bins, g, g, fm, rm).compile().as_text()
        assert "all-reduce" in hlo, (
            "distributed grow_tree must all-reduce histograms")


def test_grad_accum_keeps_batch_sharded():
    """accum_steps with a dp mesh must NOT all-gather the batch: the
    microbatch reshape carries a sharding constraint so each device
    keeps only its batch shard through the scan."""
    import optax
    from jax.sharding import Mesh

    from mmlspark_tpu.dl.text_encoder import TextEncoder
    from mmlspark_tpu.dl.train import init_train_state, make_train_step

    mesh = Mesh(np.asarray(jax.devices()), ("dp",))
    module = TextEncoder(vocab=64, width=16, depth=1, heads=2, mlp_dim=32)
    tx = optax.sgd(1e-2)
    # microbatch rows (batch/accum) must still divide the dp axis
    ids = jnp.ones((32, 8), jnp.int32)
    y = jnp.zeros(32, jnp.int32)
    state = init_train_state(module, jax.random.PRNGKey(0), ids, tx)
    step = make_train_step(module, tx, mesh=mesh, fetch="pooled",
                           loss_fn=lambda p, t: p.sum(), accum_steps=2)
    hlo = step.lower(state, ids, y).compile().as_text()
    assert "all-gather" not in hlo, "batch was gathered inside the scan"
