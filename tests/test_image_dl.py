"""Image ops, ImageTransformer, UnrollImage, TPUModel, ImageFeaturizer,
train step. Reference parity targets cited per test.
"""

import jax.numpy as jnp
import numpy as np
import optax
import pytest

from mmlspark_tpu.core import DataFrame
from mmlspark_tpu.dl import TPUModel, make_train_step
from mmlspark_tpu.dl.train import init_train_state, shard_train_state
from mmlspark_tpu.image import (ImageFeaturizer, ImageSetAugmenter,
                                ImageTransformer, ResizeImageTransformer,
                                UnrollImage)
from mmlspark_tpu.image import ops
from mmlspark_tpu.models import ResNet, ModelDownloader
from mmlspark_tpu.models.resnet import BasicBlock
from mmlspark_tpu.models.zoo import LoadedModel, ModelSchema


def tiny_resnet(num_classes=4):
    return ResNet(stage_sizes=(1, 1), block=BasicBlock, width=8,
                  num_classes=num_classes, dtype=jnp.float32)


def tiny_loaded(num_classes=4):
    import jax
    module = tiny_resnet(num_classes)
    variables = module.init(jax.random.PRNGKey(0),
                            np.zeros((1, 16, 16, 3), np.float32), False)
    schema = ModelSchema(name="tiny", input_size=16,
                         layer_names=("stage1", "stage2", "pooled",
                                      "logits"))
    return LoadedModel(schema=schema, module=module, variables=variables)


@pytest.fixture(scope="module")
def images_df(rng=None):
    rng = np.random.default_rng(7)
    imgs = rng.integers(0, 255, size=(6, 16, 16, 3)).astype(np.float32)
    return DataFrame({"image": imgs, "label": np.arange(6) % 2})


class TestImageOps:
    def test_resize_shape(self):
        x = jnp.ones((2, 8, 8, 3))
        assert ops.resize(x, 4, 6).shape == (2, 4, 6, 3)

    def test_flip_codes(self):
        x = jnp.asarray(np.arange(8, dtype=np.float32).reshape(1, 2, 4, 1))
        np.testing.assert_allclose(np.asarray(ops.flip(x, 1))[0, 0, :, 0],
                                   [3, 2, 1, 0])
        np.testing.assert_allclose(np.asarray(ops.flip(x, 0))[0, :, 0, 0],
                                   [4, 0])

    def test_gray_weights(self):
        x = jnp.ones((1, 2, 2, 3)) * jnp.asarray([100.0, 50.0, 25.0])
        gray = ops.color_format(x, "bgr2gray")
        expected = 0.114 * 100 + 0.587 * 50 + 0.299 * 25
        np.testing.assert_allclose(np.asarray(gray)[0, 0, 0, 0], expected,
                                   rtol=1e-5)

    def test_blur_preserves_mean(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(1, 9, 9, 1)), jnp.float32)
        out = ops.blur(x, 3, 3)
        assert out.shape == x.shape
        # interior pixel = mean of 3x3 neighborhood
        exp = np.asarray(x)[0, 3:6, 3:6, 0].mean()
        np.testing.assert_allclose(np.asarray(out)[0, 4, 4, 0], exp,
                                   rtol=1e-4)

    def test_threshold_binary(self):
        x = jnp.asarray([[0.0, 5.0], [10.0, 3.0]]).reshape(1, 2, 2, 1)
        out = ops.threshold(x, 4.0, 255.0)
        np.testing.assert_allclose(np.asarray(out).reshape(-1),
                                   [0, 255, 255, 0])

    def test_gaussian_blur_normalized(self):
        x = jnp.ones((1, 7, 7, 2))
        out = ops.gaussian_blur(x, 5, 1.0)
        np.testing.assert_allclose(np.asarray(out), 1.0, rtol=1e-5)


class TestImageTransformer:
    def test_pipeline(self, images_df):
        t = (ImageTransformer().setInputCol("image").setOutputCol("out")
             .resize(8, 8).flip(1).blur(3, 3))
        out = t.transform(images_df)
        assert out["out"].shape == (6, 8, 8, 3)

    def test_ragged_inputs(self):
        rng = np.random.default_rng(0)
        col = np.empty(3, object)
        col[:] = [rng.normal(size=(10, 12, 3)), rng.normal(size=(6, 6, 3)),
                  rng.normal(size=(10, 12, 3))]
        df = DataFrame({"image": col})
        out = (ImageTransformer().resize(5, 5).transform(df))["image"]
        assert out.shape == (3, 5, 5, 3)

    def test_crop(self, images_df):
        t = ImageTransformer().crop(2, 3, 5, 7)
        out = t.transform(images_df)["image"]
        assert out.shape == (6, 5, 7, 3)


class TestStages:
    def test_resize_transformer(self, images_df):
        out = ResizeImageTransformer(height=4, width=4).transform(images_df)
        assert out["image"].shape == (6, 4, 4, 3)

    def test_unroll_chw_order(self):
        img = np.arange(2 * 2 * 3, dtype=np.float32).reshape(1, 2, 2, 3)
        df = DataFrame({"image": img})
        out = UnrollImage().transform(df)["unrolled"]
        # CHW: all of channel 0 first
        np.testing.assert_allclose(out[0][:4], img[0, :, :, 0].reshape(-1))

    def test_augmenter_doubles_rows(self, images_df):
        out = ImageSetAugmenter().transform(images_df)
        assert len(out) == 12
        flipped = out["image"][6:]
        np.testing.assert_allclose(flipped, images_df["image"][:, :, ::-1])


class TestTPUModel:
    def test_endpoints_and_padding(self, images_df):
        loaded = tiny_loaded()
        m = TPUModel(model=loaded, inputCol="image", outputCol="feat",
                     outputNode="pooled", minibatchSize=4)
        out = m.transform(images_df)
        assert out["feat"].shape == (6, 16)  # width 8 * 2 stages
        # batch of 4 with 6 rows: padding path exercised; values must not
        # depend on batch position
        m1 = TPUModel(model=loaded, inputCol="image", outputCol="feat",
                      outputNode="pooled", minibatchSize=6)
        out1 = m1.transform(images_df)
        np.testing.assert_allclose(out["feat"], out1["feat"], atol=1e-4)

    def test_the_apply_program_is_named_for_the_device_trace(self,
                                                             images_df):
        """The one program of a transform is ``jit_tpu_model_apply`` to
        XLA, and the compile tracker counts it under that name."""
        from mmlspark_tpu.obs import compile_tracker
        m = TPUModel(model=tiny_loaded(), inputCol="image",
                     outputCol="feat", outputNode="pooled", minibatchSize=4)
        before = compile_tracker.compiles("tpu_model_apply")
        m.transform(images_df)
        m.transform(images_df)              # the cached program: no retrace
        assert compile_tracker.compiles("tpu_model_apply") == before + 1
        run = m._apply_fn()
        batch = np.zeros((4,) + images_df["image"].shape[1:], np.float32)
        assert "module @jit_tpu_model_apply " in run.lower(batch).as_text()
        entry = next(e for e in compile_tracker.ledger()
                     if e["fn"] == "tpu_model_apply")
        assert entry["traced"] >= 1 and entry["backend_s"] > 0

    def test_fetch_dict(self, images_df):
        loaded = tiny_loaded()
        m = TPUModel(model=loaded, inputCol="image",
                     fetchDict={"pooled": "p", "logits": "l"},
                     minibatchSize=8)
        out = m.transform(images_df)
        assert out["p"].shape == (6, 16) and out["l"].shape == (6, 4)

    def test_transfer_dtype_wire_paths(self, images_df):
        """uint8 columns ride the wire un-widened and bf16 narrowing
        matches the float32 path (the model casts to bf16 on device
        anyway, so the wire dtype must not change results materially)."""
        loaded = tiny_loaded()
        kw = dict(model=loaded, inputCol="image", outputCol="feat",
                  outputNode="pooled", minibatchSize=8)
        f32 = TPUModel(**kw).transform(images_df)["feat"]
        bf = TPUModel(transferDtype="bfloat16", **kw) \
            .transform(images_df)["feat"]
        np.testing.assert_allclose(f32, bf, atol=2e-2)
        u8 = DataFrame({"image": (np.clip(images_df["image"], 0, 1)
                                  * 255).astype(np.uint8)})
        out = TPUModel(**kw).transform(u8)["feat"]  # auto keeps uint8
        assert out.dtype == np.float32 and out.shape == (6, 16)
        # every narrowing mode must keep uint8 un-widened on the wire
        for mode in ("auto", "uint8", "bfloat16"):
            m = TPUModel(transferDtype=mode, **kw)
            assert m._coerce_input(u8["image"]).dtype == np.uint8, mode


class TestImageFeaturizer:
    def test_cut_layers(self, images_df):
        loaded = tiny_loaded()
        f = ImageFeaturizer(model=loaded, cutOutputLayers=1,
                            inputCol="image", outputCol="features",
                            miniBatchSize=8)
        out = f.transform(images_df)
        assert out["features"].shape == (6, 16)
        f0 = ImageFeaturizer(model=loaded, cutOutputLayers=0,
                             inputCol="image", outputCol="features",
                             miniBatchSize=8)
        assert f0.transform(images_df)["features"].shape == (6, 4)

    def test_zoo_downloader_random_init(self):
        dl = ModelDownloader()
        loaded = dl.download_by_name("ResNet18", num_classes=10,
                                     dtype=jnp.float32)
        assert loaded.schema.num_layers == 18
        assert "params" in loaded.variables


class TestTrainStep:
    def test_loss_decreases(self):
        import jax
        module = tiny_resnet(num_classes=2)
        tx = optax.adam(1e-2)
        rng = np.random.default_rng(0)
        x = rng.normal(size=(8, 16, 16, 3)).astype(np.float32)
        y = (np.arange(8) % 2).astype(np.int32)
        state = init_train_state(module, jax.random.PRNGKey(0), x[:1], tx)
        step = make_train_step(module, tx)
        losses = []
        for _ in range(5):
            state, loss = step(state, jnp.asarray(x), jnp.asarray(y))
            losses.append(float(loss))
        assert losses[-1] < losses[0]

    def test_sharded_train_step(self, eight_device_mesh=None):
        import jax
        from mmlspark_tpu.parallel import build_mesh, MeshSpec
        mesh = build_mesh(MeshSpec(dp=4, tp=2))
        module = tiny_resnet(num_classes=2)
        tx = optax.sgd(1e-2)
        rng = np.random.default_rng(0)
        x = rng.normal(size=(8, 16, 16, 3)).astype(np.float32)
        y = (np.arange(8) % 2).astype(np.int32)
        state = init_train_state(module, jax.random.PRNGKey(0), x[:1], tx)
        state = shard_train_state(state, mesh)
        step = make_train_step(module, tx, mesh=mesh)
        state, loss = step(state, jnp.asarray(x), jnp.asarray(y))
        assert np.isfinite(float(loss))

    def test_train_epoch_matches_manual_loop(self):
        """The overlapped-transfer loop must be numerically identical to
        stepping by hand — it changes WHEN transfers happen, not what
        the step computes."""
        import jax
        from mmlspark_tpu.dl import train_epoch
        module = tiny_resnet(num_classes=2)
        tx = optax.sgd(1e-2, momentum=0.9)
        rng = np.random.default_rng(1)
        batches = [(rng.normal(size=(4, 16, 16, 3)).astype(np.float32),
                    (np.arange(4) % 2).astype(np.int32))
                   for _ in range(3)]
        state_a = init_train_state(module, jax.random.PRNGKey(0),
                                   batches[0][0][:1], tx)
        state_b = init_train_state(module, jax.random.PRNGKey(0),
                                   batches[0][0][:1], tx)
        step = make_train_step(module, tx)
        manual_losses = []
        for x, y in batches:
            state_a, loss = step(state_a, jnp.asarray(x), jnp.asarray(y))
            manual_losses.append(float(loss))
        state_b, epoch_losses = train_epoch(step, state_b, batches)
        np.testing.assert_allclose(epoch_losses, manual_losses, rtol=0)
        jax.tree.map(np.testing.assert_array_equal,
                     state_a.params, state_b.params)

    def test_train_epoch_empty_and_sharded(self):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from mmlspark_tpu.dl import train_epoch
        from mmlspark_tpu.parallel import build_mesh, MeshSpec
        module = tiny_resnet(num_classes=2)
        tx = optax.sgd(1e-2)
        state = init_train_state(module, jax.random.PRNGKey(0),
                                 np.zeros((1, 16, 16, 3), np.float32), tx)
        step = make_train_step(module, tx)
        state2, losses = train_epoch(step, state, [])
        assert losses == [] and state2 is state
        # sharded placement: batches land dp-sharded over the mesh
        mesh = build_mesh(MeshSpec(dp=8))
        state = shard_train_state(state, mesh)
        step_m = make_train_step(module, tx, mesh=mesh)
        rng = np.random.default_rng(2)
        batches = [(rng.normal(size=(8, 16, 16, 3)).astype(np.float32),
                    (np.arange(8) % 2).astype(np.int32))]
        _, losses = train_epoch(
            step_m, state, batches,
            placement=NamedSharding(mesh, P("dp")))
        assert len(losses) == 1 and np.isfinite(losses[0])


class TestIO:
    def test_binary_reader_and_zip(self, tmp_path):
        from mmlspark_tpu.io import read_binary_files
        (tmp_path / "a.txt").write_bytes(b"hello")
        import zipfile
        with zipfile.ZipFile(tmp_path / "z.zip", "w") as z:
            z.writestr("inner.bin", b"world")
        df = read_binary_files(str(tmp_path))
        got = {p.split("/")[-1]: b for p, b in zip(df["path"], df["bytes"])}
        assert got["a.txt"] == b"hello"
        assert got["z.zip::inner.bin"] == b"world"

    def test_read_images(self, tmp_path):
        from PIL import Image
        from mmlspark_tpu.io import read_images
        arr = np.zeros((4, 5, 3), np.uint8)
        arr[..., 0] = 255  # red in RGB
        Image.fromarray(arr).save(tmp_path / "img.png")
        (tmp_path / "junk.txt").write_bytes(b"not an image")
        df = read_images(str(tmp_path))
        assert len(df) == 1
        img = df["image"][0]
        assert img.shape == (4, 5, 3)
        # BGR order: red is the LAST channel
        assert img[0, 0, 2] == 255 and img[0, 0, 0] == 0


def test_tpumodel_caches_jitted_apply():
    """Repeated transforms must not retrace/recompile: one jit trace
    serves every transform of the same model."""
    count = {"n": 0}

    class Counting(ResNet):
        def __call__(self, x, train=False):
            count["n"] += 1
            return super().__call__(x, train)

    m = Counting(stage_sizes=(1,), block=BasicBlock, width=8,
                 num_classes=2, dtype=jnp.float32)
    v = m.init(__import__("jax").random.PRNGKey(0),
               jnp.zeros((1, 16, 16, 3)), False)
    base = count["n"]
    tm = TPUModel(model=(m, v), inputCol="image", outputCol="out",
                  outputNode="pooled", minibatchSize=4)
    df = DataFrame({"image": np.random.default_rng(0).normal(
        size=(8, 16, 16, 3)).astype(np.float32)})
    out1 = tm.transform(df)["out"]
    out2 = tm.transform(df)["out"]
    tm.transform(df)
    assert count["n"] - base == 1, f"{count['n'] - base} traces"
    np.testing.assert_array_equal(out1, out2)


def test_vit_remat_matches_stored_activations():
    """ViT(remat=True): identical params/outputs and near-identical
    gradients to the stored-activation model — only memory differs."""
    import jax

    from mmlspark_tpu.models.vit import ViT

    rng = np.random.default_rng(31)
    x = jnp.asarray(rng.normal(size=(2, 32, 32, 3)), jnp.float32)
    y = jnp.asarray([0, 1], jnp.int32)
    # f32 compute: asserts the remat MATH tightly; bf16 recompute
    # rounding is exercised by the encoder remat test
    kw = dict(patch=16, width=32, depth=2, heads=2, mlp_dim=64,
              num_classes=4, dtype=jnp.float32)
    outs = {}
    for remat in (False, True):
        module = ViT(remat=remat, **kw)
        tx = optax.sgd(1e-2)
        state = init_train_state(module, jax.random.PRNGKey(0), x, tx)
        step = make_train_step(module, tx)
        new_state, loss = step(state, x, y)
        outs[remat] = (float(loss), new_state.params)
    np.testing.assert_allclose(outs[False][0], outs[True][0], rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5,
                                                atol=1e-7),
        outs[False][1], outs[True][1])


def test_resnet_remat_stable_names_and_stats():
    """ResNet remat must (a) keep the exact param tree of the historical
    auto-named model — converted checkpoints depend on it — (b) update
    batch_stats through the rematted blocks, (c) match the plain model's
    training step tightly in f32."""
    import jax

    from mmlspark_tpu.models.resnet import ResNet18

    rng = np.random.default_rng(33)
    x = jnp.asarray(rng.normal(size=(2, 32, 32, 3)), jnp.float32)
    y = jnp.asarray([0, 1], jnp.int32)
    outs = {}
    for remat in (False, True):
        module = ResNet18(num_classes=4, dtype=jnp.float32, remat=remat)
        tx = optax.sgd(1e-2)
        state = init_train_state(module, jax.random.PRNGKey(0), x, tx)
        step = make_train_step(module, tx)
        new_state, loss = step(state, x, y)
        outs[remat] = (float(loss), new_state)
    s_plain, s_remat = outs[False][1], outs[True][1]
    # (a) identical trees: same leaves, same names (incl. BasicBlock_0…)
    assert jax.tree_util.tree_structure(s_plain.params) \
        == jax.tree_util.tree_structure(s_remat.params)
    assert "BasicBlock_0" in s_plain.params
    # (b) stats moved off their init under remat
    init_stats = init_train_state(
        ResNet18(num_classes=4, dtype=jnp.float32, remat=True),
        jax.random.PRNGKey(0), x, optax.sgd(1e-2)).batch_stats
    moved = jax.tree.map(lambda a, b: bool(np.any(a != b)),
                         init_stats, s_remat.batch_stats)
    assert any(jax.tree.leaves(moved))
    # (c) tight f32 agreement
    np.testing.assert_allclose(outs[False][0], outs[True][0], rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5,
                                                atol=1e-7),
        s_plain.params, s_remat.params)
