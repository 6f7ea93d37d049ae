"""Weights from the seed, made by the benchmark and not by the program.

One jitted call fills a whole parameter tree on the device. The tree's
SHAPE is the program's (``jax.eval_shape`` of its ``init``); every value
is the benchmark's: leaf ``path`` of seed ``s`` is
``normal(fold_in(key(s), crc32(path)))`` scaled by the leaf's rule. The
plain references call the same function with the same seed and never see
an array the program has touched.

Rules (by the leaf's last key): LayerNorm ``scale`` is 1 + 0.02 n;
everything else (matrices, embeddings, biases) is 0.02 n, the published
BERT / GPT-2 initializer range. Biases are not left at zero so that a
dropped bias shows in the comparison.
"""

import importlib
import zlib

import jax
import jax.numpy as jnp

STD = 0.02


def seed_key(seed):
    """A PRNG key from any whole number up to 2**63 (the driver's seeds
    pass 2**31)."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def path_name(path):
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def make_params(shapes, seed, dtype=jnp.float32):
    """Fill ``shapes`` (a pytree of ShapeDtypeStructs) from ``seed`` in
    one jitted call, on the default device."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [path_name(p) for p, _ in leaves]

    def fill(key):
        out = []
        for name, (_, leaf) in zip(names, leaves):
            k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
            x = STD * jax.random.normal(k, leaf.shape, jnp.float32)
            if name.rsplit("/", 1)[-1] == "scale":
                x = 1.0 + x
            out.append(x.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(fill)(seed_key(seed))


def load_object(spec):
    """``"module:name"`` -> the object."""
    module, name = spec.split(":")
    return getattr(importlib.import_module(module), name)


def model_and_params(config, seed):
    """The program's model, built from the configuration file's
    ``constructor``, and its parameter tree filled from ``seed``: the
    shape is the program's, every value the benchmark's."""
    ctor = config["constructor"]
    model = load_object(ctor["model"])(load_object(ctor["config"])(
        **ctor["kwargs"]))
    shapes = jax.eval_shape(lambda: model.init(jax.random.key(0)))["params"]
    return model, make_params(shapes, seed)
