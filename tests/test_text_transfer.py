"""The text pretrained-weights chain (review round 3 Missing #4): corpus →
BPE → masked-LM pretraining → CheckpointManager/zoo round-trip →
TextEncoderFeaturizer with REAL (non-random) weights, whose frozen
features beat the random-init floor (nearest-centroid margin — the
run-to-run-stable read) and carry a GBDT classifier well above chance.
This mirrors the proven vision chain
(torch → converter → zoo → ImageFeaturizer) for text; reference analog:
pretrained models feeding featurizers (``ModelDownloader.scala:37-60``,
``image/ImageFeaturizer.scala:81-85``).

The corpus is REAL text assembled from files already in the image
(Python sources from this package, C headers from /usr/include, English
prose from docs/) — zero-egress, no synthetic strings. The downstream
task is document-language classification with few labeled examples, so
representation quality is what decides accuracy.
"""

import glob
import os

import numpy as np
import pytest

from mmlspark_tpu.core import DataFrame

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHUNK = 256  # characters per document


def _chunks(paths, limit):
    out = []
    for p in paths:
        try:
            with open(p, encoding="utf-8", errors="ignore") as f:
                text = f.read()
        except OSError:
            continue
        for i in range(0, len(text) - CHUNK, CHUNK):
            out.append(text[i:i + CHUNK])
            if len(out) >= limit:
                return out
    return out


@pytest.fixture(scope="module")
def corpus():
    py = _chunks(sorted(glob.glob(
        os.path.join(REPO, "mmlspark_tpu", "**", "*.py"),
        recursive=True)), 160)
    c = _chunks(sorted(glob.glob("/usr/include/*.h"))
                or sorted(glob.glob(
                    os.path.join(REPO, "mmlspark_tpu", "native", "src",
                                 "*.cpp"))), 160)
    prose = _chunks(sorted(glob.glob(os.path.join(REPO, "docs", "*.md"))
                           + [os.path.join(REPO, "README.md")]), 160)
    assert min(len(py), len(c), len(prose)) >= 60, \
        (len(py), len(c), len(prose))
    n = min(len(py), len(c), len(prose))
    texts = py[:n] + c[:n] + prose[:n]
    labels = np.repeat([0.0, 1.0, 2.0], n)
    # deterministic shuffle + split
    rng = np.random.default_rng(7)
    order = rng.permutation(len(texts))
    texts = [texts[i] for i in order]
    labels = labels[order]
    return texts, labels


def _text_df(texts, labels=None):
    col = np.empty(len(texts), object)
    col[:] = texts
    d = {"text": col}
    if labels is not None:
        d["label"] = np.asarray(labels, np.float32)
    return DataFrame(d)


VOCAB = 512          # BPE budget
ENC_VOCAB = VOCAB + 1  # spare top slot = the MLM mask id
WIDTH, DEPTH, HEADS = 64, 2, 2
MAXLEN = 64


@pytest.fixture(scope="module")
def tokenizer(corpus):
    from mmlspark_tpu.featurize import BpeTokenizer
    texts, _ = corpus
    return BpeTokenizer(vocabSize=VOCAB, maxLength=MAXLEN,
                        inputCol="text", outputCol="tokens") \
        .fit(_text_df(texts))


@pytest.fixture(scope="module")
def pretrained_dir(corpus, tokenizer, tmp_path_factory):
    """MLM-pretrain a small encoder on the UNLABELED corpus, checkpoint
    the LM state, publish the trunk as a zoo checkpoint."""
    import jax

    from mmlspark_tpu.dl import TextEncoder, encoder_variables, \
        pretrain_masked_lm
    from mmlspark_tpu.dl.checkpoint import CheckpointManager
    from mmlspark_tpu.models.convert import save_converted

    texts, _ = corpus
    ids = np.stack(list(
        tokenizer.transform(_text_df(texts))["tokens"]))
    encoder = TextEncoder(vocab=ENC_VOCAB, width=WIDTH, depth=DEPTH,
                          heads=HEADS, mlp_dim=4 * WIDTH)
    state, losses = pretrain_masked_lm(
        encoder, ids, steps=500, batch_size=48, learning_rate=1e-2,
        mask_frac=0.25, seed=0)
    # the LM must actually have learned: the corpus is ~26k tokens with
    # ~5.7 nats unigram entropy, so expect a clear but not dramatic drop
    assert np.mean(losses[-50:]) < np.mean(losses[:50]) - 0.4, \
        (np.mean(losses[:50]), np.mean(losses[-50:]))

    root = tmp_path_factory.mktemp("text_ckpt")
    # full LM state checkpoints (resume story)...
    mgr = CheckpointManager(str(root / "lm"), max_to_keep=2)
    mgr.save(state)
    restored = mgr.restore(target=state)
    jax.tree.map(np.testing.assert_array_equal,
                 state.params, restored.params)
    # ...and the trunk publishes to the zoo checkpoint layout
    model_dir = str(root / "zoo")
    save_converted(encoder_variables(state), "TextEncoderTest",
                   model_dir)
    return model_dir


@pytest.fixture(scope="module")
def zoo_entry():
    from mmlspark_tpu.models.zoo import register_text_encoder
    return register_text_encoder("TextEncoderTest", vocab=ENC_VOCAB,
                                 width=WIDTH, depth=DEPTH, heads=HEADS,
                                 mlp_dim=4 * WIDTH, seq_len=MAXLEN)


def _accuracy(featurizer, tokenizer, texts, labels):
    """Few-shot downstream: 8 labeled docs/class; returns
    (nearest-centroid accuracy, GBDT accuracy) on the rest. The
    centroid metric is the representation-quality read (stable under
    run-to-run float noise); the GBDT one exercises the classifier
    chain end-to-end but is only held to an above-chance floor — with
    24 train rows its exact value is sensitive to tiny feature
    perturbations."""
    from mmlspark_tpu.lightgbm import LightGBMClassifier

    ids = tokenizer.transform(_text_df(texts, labels))
    feats = featurizer.transform(ids)
    x = np.stack(list(feats["features"]))
    y = np.asarray(labels)
    train_idx = np.concatenate(
        [np.flatnonzero(y == c)[:8] for c in (0.0, 1.0, 2.0)])
    test_mask = np.ones(len(y), bool)
    test_mask[train_idx] = False
    cents = np.stack([x[train_idx][y[train_idx] == c].mean(0)
                      for c in (0.0, 1.0, 2.0)])
    d = ((x[test_mask][:, None, :] - cents[None]) ** 2).sum(-1)
    centroid = float(np.mean(d.argmin(1) == y[test_mask]))
    # minDataInLeaf must fit the 24-row few-shot set (the default 20
    # would forbid every split and pin accuracy at chance)
    clf = LightGBMClassifier(numIterations=20, numLeaves=7,
                             minDataInLeaf=2, seed=0)
    model = clf.fit(DataFrame({"features": x[train_idx],
                               "label": y[train_idx]}))
    pred = model.transform(
        DataFrame({"features": x[test_mask]}))["prediction"]
    return centroid, float(np.mean(np.asarray(pred) == y[test_mask]))


class TestTextTransferChain:
    def test_pretrained_features_beat_random_floor(
            self, corpus, tokenizer, pretrained_dir, zoo_entry):
        from mmlspark_tpu.dl import TextEncoderFeaturizer
        from mmlspark_tpu.models import ModelDownloader

        texts, labels = corpus
        loaded = ModelDownloader(pretrained_dir).download_by_name(
            "TextEncoderTest", allow_random_init=False)
        pre = TextEncoderFeaturizer(model=loaded, inputCol="tokens",
                                    outputCol="features",
                                    seqChunk=MAXLEN)
        rand = TextEncoderFeaturizer(vocabSize=ENC_VOCAB, width=WIDTH,
                                     depth=DEPTH, heads=HEADS,
                                     inputCol="tokens",
                                     outputCol="features",
                                     seqChunk=MAXLEN)
        cent_pre, gbdt_pre = _accuracy(pre, tokenizer, texts, labels)
        cent_rand, gbdt_rand = _accuracy(rand, tokenizer, texts, labels)
        # representation quality: centroid accuracy is the stable
        # metric (measured ~0.83 vs ~0.46; the 24-row GBDT margin
        # flakes under XLA:CPU thread-contention float noise — seen
        # once in CI under a saturated host)
        assert cent_pre > cent_rand + 0.15, \
            (cent_pre, cent_rand, gbdt_pre, gbdt_rand)
        assert cent_pre >= 0.7, cent_pre
        # the classifier chain itself works well above chance (1/3)
        # and above GBDT-on-random-features. The 24-row GBDT readout
        # swings with sub-ulp float differences across compile
        # environments (0.51 with remote-compiled cache artifacts vs
        # 0.493 fresh-local on the same code — round 5), so the bound
        # is what the metric can actually bear, not a knife edge.
        assert gbdt_pre >= 0.45, (gbdt_pre, gbdt_rand)
        assert gbdt_pre > gbdt_rand + 0.08, (gbdt_pre, gbdt_rand)

    def test_featurizer_modelname_and_type_guard(
            self, zoo_entry, pretrained_dir, tokenizer, corpus,
            monkeypatch):
        import jax.numpy as jnp

        from mmlspark_tpu.dl import TextEncoderFeaturizer
        from mmlspark_tpu.models import ModelDownloader

        # naming a zoo model without its checkpoint fails LOUD — never
        # a silent random-init behind a "pretrained" param
        monkeypatch.delenv("MMLSPARK_TPU_MODEL_DIR", raising=False)
        with pytest.raises(FileNotFoundError):
            TextEncoderFeaturizer(modelName="TextEncoderTest")._encoder()
        # with the checkpoint dir set, modelName resolves end-to-end
        monkeypatch.setenv("MMLSPARK_TPU_MODEL_DIR", pretrained_dir)
        feat = TextEncoderFeaturizer(modelName="TextEncoderTest",
                                     inputCol="tokens",
                                     outputCol="features",
                                     seqChunk=MAXLEN)
        texts, _ = corpus
        out = feat.transform(tokenizer.transform(_text_df(texts[:4])))
        assert np.stack(list(out["features"])).shape == (4, WIDTH)
        # a vision model is rejected with a pointed error
        vis = ModelDownloader().download_by_name(
            "ResNet18", allow_random_init=True, dtype=jnp.float32)
        with pytest.raises(TypeError, match="not a text encoder"):
            TextEncoderFeaturizer(model=vis)._encoder()

    def test_featurizer_with_loaded_model_persists(self, zoo_entry,
                                                   pretrained_dir,
                                                   tmp_path):
        """A stage holding the pretrained LoadedModel must survive
        save/load (ComplexParam pickling — a closure-based zoo builder
        broke this)."""
        from mmlspark_tpu.core import load_stage
        from mmlspark_tpu.dl import TextEncoderFeaturizer
        from mmlspark_tpu.models import ModelDownloader

        loaded = ModelDownloader(pretrained_dir).download_by_name(
            "TextEncoderTest", allow_random_init=False)
        feat = TextEncoderFeaturizer(model=loaded, inputCol="tokens",
                                     outputCol="features",
                                     seqChunk=MAXLEN)
        rows = np.zeros(2, object)
        rows[:] = [[1, 2, 3], [4, 5]]
        df = DataFrame({"tokens": rows})
        before = np.stack(list(feat.transform(df)["features"]))
        feat.save(str(tmp_path / "feat"))
        re_feat = load_stage(str(tmp_path / "feat"))
        after = np.stack(list(re_feat.transform(df)["features"]))
        np.testing.assert_allclose(after, before, atol=1e-6)

    def test_zoo_text_random_init_and_manifest_guard(self, zoo_entry,
                                                     pretrained_dir):
        from mmlspark_tpu.models import ModelDownloader

        # no checkpoint dir → deterministic random init with text dummy
        loaded = ModelDownloader().download_by_name(
            "TextEncoderTest", allow_random_init=True)
        assert "params" in loaded.variables
        # checkpointed load verifies the SHA manifest
        loaded2 = ModelDownloader(pretrained_dir).download_by_name(
            "TextEncoderTest", allow_random_init=False)
        emb = np.asarray(
            loaded2.variables["params"]["embed"]["embedding"])
        emb_r = np.asarray(
            loaded.variables["params"]["embed"]["embedding"])
        assert not np.allclose(emb, emb_r)  # real weights, not the init
