"""BERT-base MLM pretraining with the full round-3 feature set:

  * masked Pallas flash attention (default-on, handles the padded batch)
  * bf16 mixed precision (amp policy + master weights)
  * Trainer runtime: threaded ingestion, periodic checkpoint + auto-resume,
    cross-process heartbeat when launched multi-host
  * synthetic token stream (zero egress)

Single chip:
    python examples/pretrain_bert_flash.py --steps 50

Multi-host (each worker):
    python -m paddle_tpu.parallel.launch --nproc 2 \
        examples/pretrain_bert_flash.py -- --steps 50 --heartbeat-dir /tmp/hb
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/bert_flash_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--heartbeat-dir", default=None)
    ap.add_argument("--tiny", action="store_true",
                    help="tiny config (CPU-friendly smoke run)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    import paddle_tpu as pt
    from paddle_tpu.core.compile_cache import enable_compile_cache
    from paddle_tpu.models.bert import (BertConfig, BertForPretraining,
                                        pretrain_loss)
    from paddle_tpu.static.trainer import Trainer, TrainerConfig

    enable_compile_cache()

    cfg = BertConfig.tiny() if args.tiny else BertConfig.base()
    cfg.dropout = 0.0
    cfg.max_position = max(cfg.max_position, args.seq)
    model = BertForPretraining(cfg)
    variables = model.init(jax.random.key(0))
    params = variables["params"]

    opt = pt.amp.decorate(pt.optimizer.Adam(1e-4), pt.amp.bf16_policy())
    opt_state = opt.init(params)

    def loss_fn(p, ids, mlm_l, nsp_l, mmask, amask):
        mlm, nsp = model.apply({"params": p, "state": {}}, ids,
                               attention_mask=amask)
        return pretrain_loss(mlm, nsp, mlm_l, nsp_l, mmask), 0.0

    @jax.jit
    def train_step(state, ids, mlm_l, nsp_l, mmask, amask):
        loss, params, opt_state, _ = opt.minimize(
            loss_fn, state["params"], state["opt"], ids, mlm_l, nsp_l,
            mmask, amask)
        return loss, {"params": params, "opt": opt_state}

    def reader():
        rng = np.random.RandomState(jax.process_index())
        B, T = args.batch, args.seq
        while True:
            ids = rng.randint(0, cfg.vocab_size, (B, T)).astype(np.int32)
            mlm_l = rng.randint(0, cfg.vocab_size, (B, T)).astype(np.int32)
            nsp_l = rng.randint(0, 2, (B,)).astype(np.int32)
            mmask = (rng.rand(B, T) < 0.15).astype(np.float32)
            # ragged padded batch — the masked flash path handles it
            lens = rng.randint(T // 2, T + 1, (B,))
            amask = (np.arange(T)[None, :] < lens[:, None]).astype(
                np.float32)
            yield ids, mlm_l, nsp_l, mmask, amask

    tcfg = TrainerConfig(
        max_steps=args.steps, log_every=10, num_ingest_threads=1,
        checkpoint_dir=args.ckpt_dir, checkpoint_every=args.ckpt_every,
        heartbeat=args.heartbeat_dir is not None,
        heartbeat_dir=args.heartbeat_dir)
    trainer = Trainer(train_step, tcfg)
    state, stats = trainer.train({"params": params, "opt": opt_state},
                                 lambda: reader())
    print(f"done: {stats['run_steps']} steps this run "
          f"(total {stats['steps']}), {stats['steps_per_s']:.2f} steps/s, "
          f"final loss {stats['final_loss']:.4f}")


if __name__ == "__main__":
    main()
