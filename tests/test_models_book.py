"""Book-model integration tests — train each model family a few steps on tiny
synthetic data and assert the loss drops (the reference's tests/book/ e2e
fixtures: test_machine_translation.py, test_label_semantic_roles.py,
test_recommender_system.py, test_image_classification.py, test_fit_a_line.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models.seq2seq import AttentionSeq2Seq, Seq2SeqConfig, nmt_loss
from paddle_tpu.models.tagging import BiLstmCrfTagger, TaggerConfig
from paddle_tpu.models.recommender import RecommenderNet, RecConfig, rating_loss


def train_steps(loss_fn, params, steps=12, lr=0.1, opt=None):
    opt = opt or pt.optimizer.Adam(lr)
    opt_state = opt.init(params)

    @jax.jit
    def step(p, s):
        l, g = jax.value_and_grad(loss_fn)(p)
        p, s = opt.apply_gradients(p, g, s)
        return l, p, s

    first = None
    for _ in range(steps):
        l, params, opt_state = step(params, opt_state)
        if first is None:
            first = float(l)
    return first, float(l), params


class TestSeq2Seq:
    @pytest.mark.slow
    def test_nmt_loss_drops_and_decodes(self):
        cfg = Seq2SeqConfig.tiny()
        model = AttentionSeq2Seq(cfg)
        variables = model.init(jax.random.key(0))
        rng = np.random.RandomState(0)
        B, S, T = 8, 6, 5
        src = jnp.asarray(rng.randint(2, cfg.src_vocab, (B, S), dtype=np.int32))
        src_len = jnp.asarray(rng.randint(3, S + 1, B).astype(np.int32))
        tgt_in = jnp.asarray(np.concatenate(
            [np.ones((B, 1), np.int32),                    # BOS=1
             rng.randint(2, cfg.tgt_vocab, (B, T - 1), dtype=np.int32)], 1))
        tgt_out = jnp.asarray(np.concatenate(
            [np.asarray(tgt_in)[:, 1:], np.zeros((B, 1), np.int32)], 1))
        tgt_len = jnp.full((B,), T - 1, jnp.int32)

        def loss_fn(params):
            logits = model.apply({"params": params, "state": {}},
                                 src, src_len, tgt_in)
            return nmt_loss(logits, tgt_out, tgt_len)

        first, last, params = train_steps(loss_fn, variables["params"],
                                          steps=15, lr=0.05)
        assert last < first, (first, last)

        v = {"params": params, "state": {}}
        toks = model.apply(v, src, src_len, bos_id=1, eos_id=0, max_len=T,
                           method="greedy_decode")
        assert toks.shape == (B, T)
        seqs, scores = model.apply(v, src, src_len, bos_id=1, eos_id=0,
                                   beam_size=3, max_len=T,
                                   method="beam_decode")
        assert seqs.shape == (B, 3, T)
        # beam-0 score must be >= other beams (sorted by top_k)
        s = np.asarray(scores)
        assert np.all(s[:, 0] >= s[:, 1] - 1e-5)


class TestTagger:
    @pytest.mark.slow
    def test_crf_tagger_learns_identity_tags(self):
        cfg = TaggerConfig.tiny()
        model = BiLstmCrfTagger(cfg)
        variables = model.init(jax.random.key(1))
        rng = np.random.RandomState(1)
        B, T = 8, 7
        toks = rng.randint(0, cfg.vocab_size, (B, T), dtype=np.int32)
        labels = toks % cfg.num_tags                       # learnable mapping
        lengths = rng.randint(3, T + 1, B).astype(np.int32)
        toks, labels, lengths = map(jnp.asarray, (toks, labels, lengths))

        def loss_fn(params):
            return model.apply({"params": params, "state": {}},
                               toks, lengths, labels=labels)

        first, last, params = train_steps(loss_fn, variables["params"],
                                          steps=25, lr=0.1)
        assert last < first * 0.8, (first, last)
        path = model.apply({"params": params, "state": {}}, toks, lengths)
        mask = np.arange(T)[None] < np.asarray(lengths)[:, None]
        acc = (np.asarray(path) == np.asarray(labels))[mask].mean()
        assert acc > 0.5, acc


class TestRecommender:
    def test_rating_regression_converges(self):
        cfg = RecConfig.tiny()
        model = RecommenderNet(cfg)
        variables = model.init(jax.random.key(2))
        rng = np.random.RandomState(2)
        B, L = 16, 4
        batch = dict(
            usr_id=rng.randint(0, cfg.num_users, B),
            gender=rng.randint(0, cfg.num_genders, B),
            age=rng.randint(0, cfg.num_ages, B),
            job=rng.randint(0, cfg.num_jobs, B),
            mov_id=rng.randint(0, cfg.num_movies, B),
            categories=rng.randint(0, cfg.num_categories, (B, L)),
            cat_mask=(rng.rand(B, L) > 0.3).astype(np.float32),
            title_ids=rng.randint(0, cfg.title_vocab, (B, L)),
            title_mask=np.ones((B, L), np.float32),
        )
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        rating = jnp.asarray(rng.randint(1, 6, B).astype(np.float32))

        def loss_fn(params):
            pred = model.apply({"params": params, "state": {}}, **batch)
            return rating_loss(pred, rating)

        first, last, _ = train_steps(loss_fn, variables["params"], steps=30,
                                     lr=0.05)
        assert last < first, (first, last)


class TestVisionModels:
    @pytest.mark.slow
    def test_vgg16_forward_and_grad(self):
        model = pt.models.vgg16(num_classes=10)
        variables = model.init(jax.random.key(3))
        x = jnp.asarray(np.random.RandomState(3).rand(2, 3, 32, 32)
                        .astype(np.float32))
        out = model.apply(variables, x)
        assert out.shape == (2, 10)

        def loss_fn(params):
            o = model.apply({"params": params, "state": variables["state"]}, x)
            return jnp.mean(o ** 2)

        g = jax.grad(loss_fn)(variables["params"])
        flat = jax.tree_util.tree_leaves(g)
        assert all(np.all(np.isfinite(np.asarray(l))) for l in flat)

    @pytest.mark.slow
    def test_se_resnext_tiny_forward(self):
        model = pt.models.vision_cls.SEResNeXt(
            layers=(1, 1), cardinality=4, num_classes=5)
        variables = model.init(jax.random.key(4))
        x = jnp.ones((2, 3, 32, 32), jnp.float32)
        out = model.apply(variables, x)
        assert out.shape == (2, 5)
        assert np.all(np.isfinite(np.asarray(out)))

    def test_se_block_gates_channels(self):
        from paddle_tpu.models.vision_cls import SEBlock
        blk = SEBlock(8, reduction=2)
        v = blk.init(jax.random.key(5))
        x = jnp.ones((1, 8, 4, 4))
        out = blk.apply(v, x)
        # sigmoid gate in (0,1) scales each channel uniformly over space
        o = np.asarray(out)
        assert np.all(o > 0) and np.all(o < 1)
        assert np.allclose(o[0, :, 0, 0], o[0, :, 2, 2])


class TestFitALine:
    def test_linear_regression(self):
        model = pt.models.LinearRegression(in_features=4)
        variables = model.init(jax.random.key(6))
        rng = np.random.RandomState(6)
        X = rng.randn(64, 4).astype(np.float32)
        w_true = np.array([1.0, -2.0, 3.0, 0.5], np.float32)
        y = X @ w_true + 0.7
        X, y = jnp.asarray(X), jnp.asarray(y)

        def loss_fn(params):
            pred = model.apply({"params": params, "state": {}}, X)
            return jnp.mean((pred - y) ** 2)

        first, last, params = train_steps(
            loss_fn, variables["params"], steps=200,
            opt=pt.optimizer.Adam(0.1))
        assert last < 0.05, (first, last)
        np.testing.assert_allclose(
            np.asarray(params["fc"]["weight"])[:, 0], w_true, atol=0.2)


class TestErnie:
    """ERNIE 1.0 (BASELINE capability target): BERT backbone + span-level
    knowledge masking."""

    def test_knowledge_mask_masks_whole_spans(self):
        from paddle_tpu.models.ernie import knowledge_mask
        ids = np.arange(1, 21).reshape(1, 20).astype(np.int32)
        spans = [[(2, 6), (10, 13)]]
        # high prob so every unit gets selected
        masked, labels, w = knowledge_mask(ids, spans, mask_id=0,
                                           vocab_size=100, mask_prob=1.0,
                                           seed=1)
        np.testing.assert_array_equal(labels, ids)
        # spans are masked atomically: weights constant within each span
        assert w[0, 2:6].min() == w[0, 2:6].max()
        assert w[0, 10:13].min() == w[0, 10:13].max()
        assert w.sum() == 20  # mask_prob=1: everything selected
        # 80% of units become mask_id: spans replaced as a unit
        span_vals = masked[0, 2:6]
        assert (span_vals == span_vals[0]).all() or \
            (span_vals == ids[0, 2:6]).all()

    def test_ernie_pretrain_step(self):
        import paddle_tpu as pt
        from paddle_tpu.models.ernie import (ErnieConfig,
                                             ErnieForPretraining,
                                             ernie_pretrain_loss,
                                             knowledge_mask)
        cfg = ErnieConfig.tiny()
        cfg.dropout = 0.0
        model = ErnieForPretraining(cfg)
        params = model.init(jax.random.key(0))["params"]
        rng = np.random.RandomState(0)
        ids = rng.randint(5, cfg.vocab_size, (4, 16)).astype(np.int32)
        spans = [[(0, 3)], [(4, 8)], [], [(2, 4), (10, 14)]]
        masked, labels, w = knowledge_mask(ids, spans, mask_id=1,
                                           vocab_size=cfg.vocab_size,
                                           mask_prob=0.9, seed=0)
        nsp = jnp.asarray(rng.randint(0, 2, (4,)))
        opt = pt.optimizer.Adam(1e-3)
        st = opt.init(params)

        def loss_fn(p):
            mlm, nspl = model.apply({"params": p, "state": {}},
                                    jnp.asarray(masked))
            return ernie_pretrain_loss(mlm, nspl, jnp.asarray(labels), nsp,
                                       jnp.asarray(w)), None

        step = jax.jit(lambda p, s: opt.minimize(lambda q: loss_fn(q), p, s))
        l0 = None
        for _ in range(8):
            loss, params, st, _ = step(params, st)
            if l0 is None:
                l0 = float(loss)
        assert float(loss) < l0


class TestSentiment:
    """understand_sentiment book models (ref tests/book/
    test_understand_sentiment.py)."""

    def _data(self, cfg):
        rng = np.random.RandomState(0)
        ids = jnp.asarray(rng.randint(1, cfg.vocab_size, (8, 12)))
        lengths = jnp.asarray(rng.randint(4, 13, (8,)))
        labels = jnp.asarray(rng.randint(0, 2, (8, 1)))
        return ids, lengths, labels

    @pytest.mark.parametrize("cls_name", ["TextCNNSentiment",
                                          "StackedLSTMSentiment"])
    def test_trains(self, cls_name):
        from paddle_tpu.models import sentiment as S
        cfg = S.SentimentConfig.tiny()
        model = getattr(S, cls_name)(cfg)
        params = model.init(jax.random.key(0))["params"]
        ids, lengths, labels = self._data(cfg)
        opt = pt.optimizer.Adam(5e-3)
        st = opt.init(params)

        def loss_fn(p):
            logits = model.apply({"params": p, "state": {}}, ids, lengths)
            return S.sentiment_loss(logits, labels), None

        step = jax.jit(lambda p, s: opt.minimize(lambda q: loss_fn(q), p, s))
        l0 = None
        for _ in range(12):
            loss, params, st, _ = step(params, st)
            if l0 is None:
                l0 = float(loss)
        assert float(loss) < l0

    def test_padding_invariance(self):
        """Masked models must ignore pad tokens entirely."""
        from paddle_tpu.models import sentiment as S
        cfg = S.SentimentConfig.tiny()
        model = S.TextCNNSentiment(cfg)
        v = model.init(jax.random.key(1))
        rng = np.random.RandomState(2)
        ids = rng.randint(1, cfg.vocab_size, (2, 10)).astype(np.int32)
        lengths = jnp.asarray([6, 10])
        ids2 = ids.copy()
        ids2[0, 6:] = 7  # change padding content only
        o1 = model.apply(v, jnp.asarray(ids), lengths)
        o2 = model.apply(v, jnp.asarray(ids2), lengths)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   atol=1e-6)


def _run_example(script, args, timeout=300):
    """Run an examples/ script on the 8-device CPU mesh (shared by the
    example-regression tests)."""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=repo)
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "examples", script), *args],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=repo)
    assert r.returncode == 0, (script, r.stderr[-1500:])
    return r


@pytest.mark.slow
def test_examples_run(tmp_path):
    """The examples/ scripts are living documentation — keep them running."""
    r = _run_example("train_resnet.py",
                     ["--steps", "4", "--batch", "8",
                      "--ckpt", str(tmp_path / "ck")])
    assert "checkpoint saved" in r.stdout
    _run_example("train_ctr_sparse.py", ["--steps", "3", "--batch", "16"])
    r = _run_example("distributed_dp_tp.py", [])
    assert "plan (first entries):" in r.stdout


@pytest.mark.slow
def test_examples_run_decode_and_detection(tmp_path):
    """The remaining example scripts: KV-cache decoding, the NMT decoder
    protocol, SSD detection, BERT pretraining (trainer+checkpoint+flash,
    ckpt-every 2 so saves actually fire inside 4 steps)."""
    r = _run_example("generate_gpt.py",
                     ["--max-new", "6", "--prompt-len", "6"], timeout=560)
    assert "tok/s" in r.stdout
    r = _run_example("serve_gpt.py",
                     ["--requests", "5", "--slots", "2", "--max-new",
                      "8"], timeout=560)
    assert "serve step traced 1x" in r.stdout
    r = _run_example("nmt_seq2seq.py", ["--steps", "300"], timeout=560)
    assert r.stdout.rstrip().endswith("OK")
    _run_example("train_ssd.py",
                 ["--steps", "4", "--batch", "2", "--tiny"], timeout=560)
    bck = str(tmp_path / "bck")
    _run_example("pretrain_bert_flash.py",
                 ["--steps", "4", "--batch", "2", "--seq", "32", "--tiny",
                  "--ckpt-dir", bck, "--ckpt-every", "2"], timeout=560)
    import os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert any(d.isdigit() for d in os.listdir(bck)), os.listdir(bck)


class TestGPT:
    """Decoder-only causal LM (long-context flagship; no reference
    counterpart — exists for the BASELINE long-context requirement)."""

    def test_trains_and_is_causal(self):
        from paddle_tpu.models.gpt import GPT, GPTConfig, lm_loss
        cfg = GPTConfig.tiny()
        cfg.dropout = 0.0
        cfg.use_flash = False            # dense path on CPU
        model = GPT(cfg)
        v = model.init(jax.random.key(0))
        rng = np.random.RandomState(0)
        ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 16),
                                      dtype=np.int32))

        def loss_fn(params):
            logits = model.apply({"params": params, "state": {}}, ids)
            return lm_loss(logits, ids)

        first, last, params = train_steps(loss_fn, v["params"], steps=10,
                                          lr=0.05)
        assert last < first, (first, last)

        # causality: changing a future token can't change past logits
        logits = model.apply({"params": params, "state": {}}, ids)
        ids2 = np.asarray(ids).copy()
        ids2[:, 10] = (ids2[:, 10] + 1) % cfg.vocab_size
        logits2 = model.apply({"params": params, "state": {}},
                              jnp.asarray(ids2))
        np.testing.assert_allclose(np.asarray(logits)[:, :10],
                                   np.asarray(logits2)[:, :10],
                                   rtol=1e-5, atol=1e-5)
        assert not np.allclose(np.asarray(logits)[:, 10:],
                               np.asarray(logits2)[:, 10:])

    def test_flash_matches_dense(self):
        from paddle_tpu.core.flags import set_flags
        from paddle_tpu.models.gpt import GPT, GPTConfig
        cfg = GPTConfig(vocab_size=256, hidden_size=128, num_layers=2,
                        num_heads=2, intermediate_size=256,
                        max_position=64, dropout=0.0)
        ids = jnp.asarray(np.random.RandomState(1).randint(
            0, 256, (2, 32), dtype=np.int32))
        model = GPT(cfg)
        v = model.init(jax.random.key(0))
        set_flags({"pallas_interpret": True})
        try:
            flash = model.apply(v, ids)
        finally:
            set_flags({"pallas_interpret": False})
        cfg.use_flash = False
        dense = GPT(cfg).apply(v, ids)
        np.testing.assert_allclose(np.asarray(flash), np.asarray(dense),
                                   rtol=5e-4, atol=5e-4)

    def test_sequence_parallel_matches_single_device(self):
        # seq_axis: the WHOLE forward under shard_map with the sequence
        # sharded over 8 devices must match the single-device forward
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        import paddle_tpu as pt
        from paddle_tpu.models.gpt import GPT, GPTConfig
        cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                        num_heads=2, intermediate_size=128,
                        max_position=128, dropout=0.0, use_flash=False)
        model = GPT(cfg)
        v = model.init(jax.random.key(0))
        ids = jnp.asarray(np.random.RandomState(2).randint(
            0, 128, (1, 8 * 8), dtype=np.int32))
        ref = model.apply(v, ids)

        cfg_sp = GPTConfig(**{**cfg.__dict__, "seq_axis": "sp"})
        model_sp = GPT(cfg_sp)
        mesh = pt.parallel.make_mesh({"sp": 8})
        f = shard_map(
            lambda p_, i_: model_sp.apply({"params": p_, "state": {}}, i_),
            mesh=mesh, in_specs=(P(), P(None, "sp")),
            out_specs=P(None, "sp", None), check_vma=False)
        got = f(v["params"], ids)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_kv_cache_decode_matches_full_forward(self):
        # teacher-forced incremental decoding must reproduce the full
        # forward's logits at every position
        from paddle_tpu.models.gpt import GPTConfig, GPTDecoder
        cfg = GPTConfig.tiny()
        cfg.dropout = 0.0
        cfg.use_flash = False
        model = GPTDecoder(cfg)
        v = model.init(jax.random.key(0))
        ids = jnp.asarray(np.random.RandomState(3).randint(
            0, cfg.vocab_size, (2, 12), dtype=np.int32))
        full = model.apply(v, ids)                       # [B, T, V]

        def incremental(ids):
            caches = model.init_caches(2, 12)
            outs = []
            for t in range(12):
                logits, caches = model.decode_step(ids[:, t:t + 1],
                                                   caches, t)
                outs.append(logits[:, 0])
            return jnp.stack(outs, 1)

        inc = model.apply(v, ids, method=incremental)
        np.testing.assert_allclose(np.asarray(inc), np.asarray(full),
                                   rtol=2e-4, atol=2e-4)

    def test_batched_prefill_matches_stepwise(self):
        """generate()'s one-pass prompt prefill must leave the caches and
        last logits exactly as Tp sequential decode_steps would (the
        serving prefill/decode split)."""
        from paddle_tpu.models.gpt import GPTConfig, GPTDecoder
        cfg = GPTConfig.tiny()
        cfg.dropout = 0.0
        cfg.use_flash = False
        model = GPTDecoder(cfg)
        v = model.init(jax.random.key(0))
        prompt = jnp.asarray(np.random.RandomState(5).randint(
            0, cfg.vocab_size, (2, 10), dtype=np.int32))

        def batched(pr):
            caches = model.init_caches(2, 10)
            x = (model.tok_emb(pr)
                 + model.pos_emb(jnp.arange(10)[None, :]))
            new = []
            for blk, c in zip(model.blocks, caches):
                x, c = blk.prefill(x, c)
                new.append(c)
            return x, new

        def stepwise(pr):
            caches = model.init_caches(2, 10)
            for t in range(10):
                _, caches = model.decode_step(pr[:, t:t + 1], caches, t)
            return caches

        _, cb = model.apply(v, prompt, method=batched)
        cs = model.apply(v, prompt, method=stepwise)
        for a, b in zip(cb, cs):
            np.testing.assert_allclose(np.asarray(a["k"]),
                                       np.asarray(b["k"]),
                                       rtol=2e-4, atol=2e-4)
            np.testing.assert_allclose(np.asarray(a["v"]),
                                       np.asarray(b["v"]),
                                       rtol=2e-4, atol=2e-4)
        # bf16 cache generation agrees with f32 on the greedy tokens
        o32 = model.apply(v, prompt, method=lambda p_: model.generate(
            p_, max_new=6))
        o16 = model.apply(v, prompt, method=lambda p_: model.generate(
            p_, max_new=6, cache_dtype=jnp.bfloat16))
        assert float(np.mean(np.asarray(o16) == np.asarray(o32))) > 0.9

    def test_greedy_generate_matches_argmax_forwards(self):
        from paddle_tpu.models.gpt import GPTConfig, GPTDecoder
        cfg = GPTConfig.tiny()
        cfg.dropout = 0.0
        cfg.use_flash = False
        model = GPTDecoder(cfg)
        v = model.init(jax.random.key(1))
        prompt = jnp.asarray(np.random.RandomState(4).randint(
            0, cfg.vocab_size, (1, 4), dtype=np.int32))

        out = model.apply(v, prompt, method=lambda p_: model.generate(
            p_, max_new=5))
        assert out.shape == (1, 9)
        # reference: repeatedly run the full forward and take argmax
        seq = np.asarray(prompt)
        for _ in range(5):
            logits = model.apply(v, jnp.asarray(seq))
            nxt = np.argmax(np.asarray(logits)[:, -1], -1)
            seq = np.concatenate([seq, nxt[:, None].astype(seq.dtype)], 1)
        np.testing.assert_array_equal(np.asarray(out), seq)
