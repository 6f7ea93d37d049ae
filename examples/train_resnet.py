"""Train ResNet on synthetic images — the image_classification book recipe.

Run (CPU or TPU):  python examples/train_resnet.py --steps 20 --batch 32
"""

import argparse

import numpy as np

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu.core.compile_cache import enable_compile_cache
from paddle_tpu.models import ResNet
from paddle_tpu.ops import loss as L


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--depth", type=int, default=18)
    ap.add_argument("--ckpt", default=None, help="checkpoint dir")
    args = ap.parse_args()

    enable_compile_cache()
    model = ResNet(args.depth, num_classes=10, small_input=True)
    variables = model.init(jax.random.key(0))
    params, state = variables["params"], variables["state"]
    opt = pt.amp.decorate(pt.optimizer.Momentum(0.05, 0.9),
                          pt.amp.bf16_policy())
    opt_state = opt.init(params)

    def loss_fn(p, images, labels, state):
        out, new_state = model.apply({"params": p, "state": state}, images,
                                     training=True)
        return jnp.mean(L.softmax_with_cross_entropy(out, labels)), new_state

    @jax.jit
    def step(params, opt_state, state, images, labels):
        loss, params, opt_state, state = opt.minimize(
            loss_fn, params, opt_state, images, labels, state)
        return loss, params, opt_state, state

    loader = pt.data.DataLoader.from_generator(
        generator=lambda: pt.data.synthetic_images(
            args.steps * args.batch, num_classes=10),
        batch_size=args.batch)
    for i, (images, labels) in enumerate(loader):
        loss, params, opt_state, state = step(params, opt_state, state,
                                              images, labels)
        if i % 5 == 0:
            print(f"step {i} loss {float(loss):.4f}")

    if args.ckpt:
        mgr = pt.io.CheckpointManager(args.ckpt)
        mgr.save(args.steps, {"params": params, "opt": opt_state,
                              "state": state})
        mgr.close()
        print(f"checkpoint saved to {args.ckpt}")


if __name__ == "__main__":
    main()
