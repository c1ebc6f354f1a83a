"""Partition-rule engine: regex rules → PartitionSpec, shard/gather,
dtype policy, per-model rule sets, and the pjit'd train step's
numerical equivalence to the unsharded step.

Runs on the 8-virtual-device CPU platform the conftest forces, so the
2×4 mesh paths execute the real SPMD code."""

import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from mmlspark_tpu.obs import registry
from mmlspark_tpu.parallel import MeshSpec, build_mesh
from mmlspark_tpu.parallel.partition import (
    DtypePolicy, gather_params, match_partition_rules, named_leaves,
    partition_rules_for, registered_rule_sets, shard_params)


class TestMatchRules:
    def test_first_match_wins(self):
        params = {"block0": {"q": {"kernel": jnp.zeros((8, 8))}}}
        rules = [(r"q/kernel", (None, "tp")),
                 (r"kernel", ("tp", None))]
        specs = match_partition_rules(rules, params)
        assert specs["block0"]["q"]["kernel"] == P(None, "tp")
        # reversed order: the general rule now shadows the specific one
        specs = match_partition_rules(list(reversed(rules)), params)
        assert specs["block0"]["q"]["kernel"] == P("tp", None)

    def test_scalars_replicate_without_matching(self):
        params = {"step": jnp.zeros(()), "one": jnp.zeros((1,)),
                  "w": jnp.zeros((4, 4))}
        specs = match_partition_rules([(r".*", ("tp", None))], params)
        assert specs["step"] == P()
        assert specs["one"] == P()
        assert specs["w"] == P("tp", None)

    def test_unmatched_leaf_falls_back_loud(self):
        params = {"mystery": jnp.zeros((4, 4))}
        before = registry.counter(
            "parallel_unmatched_leaves_total").value()
        with pytest.warns(UserWarning, match="mystery"):
            specs = match_partition_rules([(r"kernel", ("tp",))], params)
        assert specs["mystery"] == P()
        after = registry.counter(
            "parallel_unmatched_leaves_total").value()
        assert after == before + 1

    def test_unmatched_error_mode(self):
        params = {"mystery": jnp.zeros((4, 4))}
        with pytest.raises(ValueError, match="mystery"):
            match_partition_rules([(r"kernel", ("tp",))], params,
                                  on_unmatched="error")

    def test_rule_match_counter(self):
        c = registry.counter("parallel_rule_match_total")
        before = c.value(rule=r"q/kernel")
        match_partition_rules(
            [(r"q/kernel", (None, "tp"))],
            {"q": {"kernel": jnp.zeros((4, 4))}})
        assert c.value(rule=r"q/kernel") == before + 1

    def test_scan_stacked_params_right_align(self):
        """A rule written for the unstacked layer covers its
        lax.scan-stacked twin: specs right-align to trailing dims."""
        rules = [(r"qkv/kernel", (None, "tp")), (r"qkv/bias", ("tp",))]
        unstacked = {"qkv": {"kernel": jnp.zeros((8, 24)),
                             "bias": jnp.zeros((24,))}}
        stacked = {"qkv": {"kernel": jnp.zeros((4, 8, 24)),
                           "bias": jnp.zeros((4, 24))}}
        s1 = match_partition_rules(rules, unstacked)
        s2 = match_partition_rules(rules, stacked)
        assert s1["qkv"]["kernel"] == P(None, "tp")
        assert s2["qkv"]["kernel"] == P(None, None, "tp")
        assert s1["qkv"]["bias"] == P("tp")
        assert s2["qkv"]["bias"] == P(None, "tp")

    def test_spec_longer_than_leaf_is_loud(self):
        with pytest.raises(ValueError, match="more entries"):
            match_partition_rules([(r"b", (None, None, "tp"))],
                                  {"b": jnp.zeros((4, 4))})

    def test_optimizer_state_paths_match_param_rules(self):
        """Optax states nest the param tree, so the SAME rules cover the
        moments (the fmengine TrainState pattern)."""
        import optax
        params = {"block0": {"qkv": {"kernel": jnp.zeros((8, 24))}}}
        opt = optax.adamw(1e-3).init(params)
        specs = match_partition_rules(
            [(r"qkv/kernel", (None, "tp"))], opt)
        flat = dict(named_leaves(specs))
        mu = [v for k, v in flat.items() if "mu" in k and "kernel" in k]
        assert mu == [P(None, "tp")]


class TestDtypePolicy:
    def test_casts_float_leaves_only(self):
        policy = DtypePolicy(param_dtype="bfloat16")
        tree = {"w": jnp.zeros((4,), jnp.float32),
                "ids": jnp.zeros((4,), jnp.int32),
                "flag": jnp.zeros((4,), bool)}
        out = policy.cast_params(tree)
        assert out["w"].dtype == jnp.bfloat16
        assert out["ids"].dtype == jnp.int32
        assert out["flag"].dtype == jnp.bool_

    def test_none_means_leave_alone(self):
        policy = DtypePolicy(param_dtype=None)
        w = jnp.zeros((4,), jnp.float16)
        assert policy.cast_params({"w": w})["w"].dtype == jnp.float16

    def test_grad_accum_cast(self):
        policy = DtypePolicy(grad_accum_dtype="float32")
        g = jnp.zeros((4,), jnp.bfloat16)
        assert policy.cast_grad_accum({"g": g})["g"].dtype == jnp.float32


class TestShardGather:
    def test_2x4_mesh_round_trip(self):
        """shard over a dp=2 × tp=4 mesh per rules, gather back, get the
        original values — the checkpoint-publication contract."""
        mesh = build_mesh(MeshSpec(dp=2, tp=4))
        rng = np.random.default_rng(0)
        params = {"emb": {"embedding": rng.normal(size=(16, 8))
                          .astype(np.float32)},
                  "qkv": {"kernel": rng.normal(size=(8, 24))
                          .astype(np.float32), "bias": np.zeros(
                              24, np.float32)},
                  "step": np.zeros((), np.int32)}
        rules = [(r"embedding", ("tp", None)),
                 (r"qkv/kernel", (None, "tp")), (r"qkv/bias", ("tp",))]
        placed, shardings = shard_params(mesh, params, rules=rules)
        assert shardings["qkv"]["kernel"].spec == P(None, "tp")
        # kernel physically split over tp: 4 distinct shards of 24/4
        shard_shapes = {s.data.shape
                        for s in placed["qkv"]["kernel"].addressable_shards}
        assert shard_shapes == {(8, 6)}
        back = gather_params(placed)
        for (name, a), (_, b) in zip(named_leaves(params),
                                     named_leaves(back)):
            np.testing.assert_array_equal(np.asarray(a), b, err_msg=name)
            assert np.asarray(a).dtype == b.dtype, name

    def test_non_divisible_dim_demotes_loudly(self):
        mesh = build_mesh(MeshSpec(dp=2, tp=4))
        c = registry.counter("parallel_spec_demoted_total")
        before = c.value(axis="tp")
        placed, shardings = shard_params(
            mesh, {"w": np.zeros((10, 8), np.float32)},
            rules=[(r"w", ("tp", None))])   # 10 % 4 != 0
        assert shardings["w"].spec == P(None, None)
        assert c.value(axis="tp") == before + 1
        assert gather_params(placed)["w"].shape == (10, 8)

    def test_missing_mesh_axis_demotes_loudly(self):
        """A tp rule against a dp-only mesh (local_mesh) must demote to
        replicated like a non-divisible dim, not KeyError — the
        documented default data-parallel world has no tp axis."""
        from mmlspark_tpu.parallel import local_mesh
        mesh = local_mesh()            # Mesh(devices, ("dp",))
        c = registry.counter("parallel_spec_demoted_total")
        before = c.value(axis="tp")
        placed, shardings = shard_params(
            mesh, {"w": np.zeros((8, 8), np.float32)},
            rules=[(r"w", (None, "tp"))])
        assert shardings["w"].spec == P(None, None)
        assert c.value(axis="tp") == before + 1
        np.testing.assert_array_equal(gather_params(placed)["w"],
                                      np.zeros((8, 8)))

    def test_short_spec_right_aligns_like_rules(self):
        """to_shardings applies a shorter-than-rank spec to the TRAILING
        dims (the same convention rule specs document), not the leading
        ones."""
        from mmlspark_tpu.parallel import to_shardings
        mesh = build_mesh(MeshSpec(dp=2, tp=4))
        sh = to_shardings(mesh, {"w": np.zeros((6, 8), np.float32)},
                          {"w": P("tp")})
        assert sh["w"].spec == P(None, "tp")   # 8 % 4 == 0: kept
        # over-long hand specs fail loudly, like the rules path
        with pytest.raises(ValueError, match="more entries"):
            to_shardings(mesh, {"b": np.zeros(4, np.float32)},
                         {"b": P("dp", "tp")})

    def test_dtype_policy_applied_at_shard_time(self):
        mesh = build_mesh(MeshSpec(dp=2, tp=4))
        placed, _ = shard_params(
            mesh, {"w": np.zeros((8, 8), np.float32),
                   "ids": np.zeros(8, np.int32)},
            rules=[(r"w", (None, "tp")), (r"ids", ())],
            dtype_policy=DtypePolicy(param_dtype="bfloat16"))
        assert placed["w"].dtype == jnp.bfloat16
        assert placed["ids"].dtype == jnp.int32


class TestModelRuleSets:
    """Every registered model's FULL param tree matches with zero
    unmatched leaves (the acceptance bar for shipping a rule set)."""

    def _check(self, name, module, x, method=None):
        rng = jax.random.PRNGKey(0)
        variables = module.init(rng, x) if method is None \
            else module.init(rng, x, False)
        rules = partition_rules_for(name)
        for collection, tree in variables.items():
            specs = match_partition_rules(rules, tree,
                                          on_unmatched="error")
            # at least one leaf actually tp-shards (a rule set that
            # replicates everything is a typo'd no-op)
            if collection == "params":
                assert any("tp" in tuple(s)
                           for _, s in named_leaves(specs)), name

    def test_registry_covers_the_zoo(self):
        # registration happens at model-definition import time
        import mmlspark_tpu.dl.bert           # noqa: F401
        import mmlspark_tpu.dl.pretrain       # noqa: F401
        import mmlspark_tpu.models.resnet     # noqa: F401
        import mmlspark_tpu.models.vit        # noqa: F401
        assert {"ResNet", "ViT", "BertEncoder", "TextEncoder",
                "TextEncoderLM"} <= set(registered_rule_sets())

    def test_resnet(self):
        from mmlspark_tpu.models.resnet import BasicBlock, ResNet
        self._check("ResNet",
                    ResNet(stage_sizes=(1, 1), block=BasicBlock,
                           num_classes=8, width=8),
                    jnp.zeros((1, 32, 32, 3)), method=True)

    def test_vit(self):
        from mmlspark_tpu.models.vit import ViT
        self._check("ViT",
                    ViT(patch=8, width=32, depth=1, heads=2, mlp_dim=64,
                        num_classes=8),
                    jnp.zeros((1, 32, 32, 3)), method=True)

    def test_bert(self):
        from mmlspark_tpu.dl.bert import BertEncoder
        self._check("BertEncoder",
                    BertEncoder(vocab=64, width=16, depth=1, heads=2,
                                mlp_dim=32, max_len=16),
                    jnp.zeros((1, 8), jnp.int32))

    def test_text_encoder(self):
        from mmlspark_tpu.dl.text_encoder import TextEncoder
        self._check("TextEncoder",
                    TextEncoder(vocab=64, width=16, depth=1, heads=2,
                                mlp_dim=32),
                    jnp.zeros((1, 8), jnp.int32), method=True)

    def test_text_encoder_lm(self):
        from mmlspark_tpu.dl.pretrain import MaskedLMModel
        from mmlspark_tpu.dl.text_encoder import TextEncoder
        self._check("TextEncoderLM",
                    MaskedLMModel(TextEncoder(vocab=64, width=16,
                                              depth=1, heads=2,
                                              mlp_dim=32)),
                    jnp.zeros((1, 8), jnp.int32))


def _bert_fixture():
    import optax
    from mmlspark_tpu.dl.bert import BertEncoder
    from mmlspark_tpu.dl.train import init_train_state
    module = BertEncoder(vocab=64, width=32, depth=2, heads=2,
                         mlp_dim=64, max_len=32, pooler=False,
                         dtype=jnp.float32)
    tx = optax.adamw(1e-3)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(1, 64, size=(16, 16)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, 32, size=16), jnp.int32)

    def fresh_state():
        return init_train_state(module, jax.random.PRNGKey(0), ids[:1],
                                tx)
    return module, tx, ids, labels, fresh_state


class TestPartitionedTrainStep:
    def test_pjit_matches_unsharded_on_one_device(self):
        """Acceptance bar: the pjit'd BERT train step's loss trajectory
        equals the unsharded step's on a 1-device mesh (atol 1e-5,
        f32)."""
        from mmlspark_tpu.dl.train import (make_partitioned_train_step,
                                           make_train_step,
                                           partition_train_state)
        module, tx, ids, labels, fresh = _bert_fixture()
        rules = partition_rules_for("BertEncoder")

        step_ref = make_train_step(module, tx, fetch="pooled")
        s = fresh()
        ref = []
        for _ in range(4):
            s, loss = step_ref(s, ids, labels)
            ref.append(float(loss))

        mesh1 = build_mesh(MeshSpec(dp=1, tp=1),
                           devices=np.asarray(jax.devices()[:1]))
        with warnings.catch_warnings():
            warnings.simplefilter("error")   # no unmatched leaves
            ss, shardings = partition_train_state(fresh(), mesh1, rules)
        step = make_partitioned_train_step(module, tx, mesh1, shardings,
                                           fetch="pooled")
        got = []
        for _ in range(4):
            ss, loss = step(ss, ids, labels)
            got.append(float(loss))
        np.testing.assert_allclose(got, ref, atol=1e-5)

    def test_sharded_dp_tp_trajectory_close_and_layout_stable(self):
        from mmlspark_tpu.dl.train import (make_partitioned_train_step,
                                           make_train_step,
                                           partition_train_state)
        module, tx, ids, labels, fresh = _bert_fixture()
        rules = partition_rules_for("BertEncoder")

        step_ref = make_train_step(module, tx, fetch="pooled")
        s = fresh()
        ref = []
        for _ in range(3):
            s, loss = step_ref(s, ids, labels)
            ref.append(float(loss))

        mesh = build_mesh(MeshSpec(dp=2, tp=4))
        ss, shardings = partition_train_state(fresh(), mesh, rules)
        step = make_partitioned_train_step(module, tx, mesh, shardings,
                                           fetch="pooled")
        got = []
        for _ in range(3):
            ss, loss = step(ss, ids, labels)
            got.append(float(loss))
        np.testing.assert_allclose(got, ref, atol=1e-4)
        # out_shardings pin the layout: params stay where the rules put
        # them after an update (no GSPMD drift → no re-compiles)
        k = ss.params["block0"]["q"]["kernel"]
        assert k.sharding.spec == P(None, "tp")

    def test_accum_steps_with_grad_accum_dtype(self):
        from mmlspark_tpu.dl.train import (make_partitioned_train_step,
                                           partition_train_state)
        module, tx, ids, labels, fresh = _bert_fixture()
        mesh = build_mesh(MeshSpec(dp=2, tp=4))
        ss, shardings = partition_train_state(
            fresh(), mesh, partition_rules_for("BertEncoder"))
        step = make_partitioned_train_step(
            module, tx, mesh, shardings, fetch="pooled", accum_steps=2,
            dtype_policy=DtypePolicy(param_dtype=None, compute_dtype=None,
                                     grad_accum_dtype="float32"))
        ss, loss = step(ss, ids, labels)
        assert np.isfinite(float(loss))

    def test_accum_dtype_below_grad_dtype(self):
        """Regression: a LOWER-precision accumulator (bf16 accum over
        f32 grads — the HBM-saving configuration) must not promote the
        scan carry (lax.scan rejects carry-dtype drift)."""
        from mmlspark_tpu.dl.train import (make_partitioned_train_step,
                                           partition_train_state)
        module, tx, ids, labels, fresh = _bert_fixture()
        mesh = build_mesh(MeshSpec(dp=2, tp=4))
        ss, shardings = partition_train_state(
            fresh(), mesh, partition_rules_for("BertEncoder"))
        step = make_partitioned_train_step(
            module, tx, mesh, shardings, fetch="pooled", accum_steps=2,
            dtype_policy=DtypePolicy(param_dtype=None, compute_dtype=None,
                                     grad_accum_dtype="bfloat16"))
        ss, loss = step(ss, ids, labels)
        assert np.isfinite(float(loss))


class TestMeshPretrain:
    def test_masked_lm_mesh_matches_plain(self):
        from mmlspark_tpu.dl.pretrain import pretrain_masked_lm
        from mmlspark_tpu.dl.text_encoder import TextEncoder
        rng = np.random.default_rng(0)
        ids = rng.integers(1, 60, size=(64, 12)).astype(np.int32)

        def enc():
            return TextEncoder(vocab=64, width=16, depth=1, heads=2,
                               mlp_dim=32, dtype=jnp.float32)

        _, plain = pretrain_masked_lm(enc(), ids, steps=3, batch_size=8)
        mesh = build_mesh(MeshSpec(dp=4, tp=2))
        _, sharded = pretrain_masked_lm(enc(), ids, steps=3,
                                        batch_size=8, mesh=mesh)
        np.testing.assert_allclose(sharded, plain, atol=1e-4)

    def test_batch_must_divide_dp(self):
        from mmlspark_tpu.dl.pretrain import pretrain_masked_lm
        from mmlspark_tpu.dl.text_encoder import TextEncoder
        mesh = build_mesh(MeshSpec(dp=8, tp=1))
        with pytest.raises(ValueError, match="divide"):
            pretrain_masked_lm(
                TextEncoder(vocab=64, width=16, depth=1, heads=2,
                            mlp_dim=32),
                np.ones((8, 4), np.int32), steps=1, batch_size=6,
                mesh=mesh)


class TestFeaturizerDpSharding:
    def test_dp_mesh_embeds_and_unpads(self):
        from mmlspark_tpu.dl.text_encoder import TextEncoderFeaturizer
        from mmlspark_tpu.core import DataFrame
        mesh = build_mesh(MeshSpec(dp=8, tp=1))
        stage = TextEncoderFeaturizer(mesh=mesh, vocabSize=64, width=16,
                                      heads=2, depth=1, seqChunk=8)
        rows = [[1, 2, 3], [4, 5], [6], [7, 8, 9], [2]]  # 5 % 8 != 0
        df = DataFrame({"tokens": rows})
        out = stage.transform(df)
        feats = np.asarray(list(out["features"]))
        assert feats.shape == (5, 16)          # padding rows dropped
        # identical rows embed identically whether or not the batch
        # needed padding (padding is masked out, not mixed in)
        stage2 = TextEncoderFeaturizer(vocabSize=64, width=16, heads=2,
                                       depth=1, seqChunk=8)
        ref = np.asarray(list(stage2.transform(df)["features"]))
        np.testing.assert_allclose(feats, ref, atol=1e-5)
