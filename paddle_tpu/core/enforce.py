"""Error checking — the PADDLE_ENFORCE family, Python-native.

Ref: /root/reference/paddle/fluid/platform/enforce.h:286 (PADDLE_ENFORCE,
PADDLE_ENFORCE_EQ/NE/GT/GE/LT/LE/NOT_NULL with demangled stack traces).
Python tracebacks already carry the stack; we add structured error types and
shape/dtype-specific checks used throughout the op library.
"""


class EnforceError(RuntimeError):
    """Framework invariant violation (ref: platform::EnforceNotMet)."""


def enforce(cond, msg="", *args):
    if not cond:
        raise EnforceError(msg % args if args else str(msg))


def enforce_eq(a, b, msg=""):
    if a != b:
        raise EnforceError(f"Expected {a!r} == {b!r}. {msg}")


def enforce_ne(a, b, msg=""):
    if a == b:
        raise EnforceError(f"Expected {a!r} != {b!r}. {msg}")


def enforce_gt(a, b, msg=""):
    if not a > b:
        raise EnforceError(f"Expected {a!r} > {b!r}. {msg}")


def enforce_ge(a, b, msg=""):
    if not a >= b:
        raise EnforceError(f"Expected {a!r} >= {b!r}. {msg}")


def enforce_lt(a, b, msg=""):
    if not a < b:
        raise EnforceError(f"Expected {a!r} < {b!r}. {msg}")


def enforce_le(a, b, msg=""):
    if not a <= b:
        raise EnforceError(f"Expected {a!r} <= {b!r}. {msg}")


def enforce_not_none(x, name="value"):
    if x is None:
        raise EnforceError(f"{name} must not be None")
    return x


def enforce_rank(x, rank, name="tensor"):
    if x.ndim != rank:
        raise EnforceError(f"{name} must have rank {rank}, got shape {x.shape}")
    return x


def enforce_shape_match(a, b, msg=""):
    if tuple(a.shape) != tuple(b.shape):
        raise EnforceError(f"Shape mismatch: {a.shape} vs {b.shape}. {msg}")


def check_numerics(tree, label="tensors"):
    """Host-side NaN/Inf validation of a pytree of arrays.

    Ref: /root/reference/paddle/fluid/platform/flags.cc:44
    (FLAGS_check_nan_inf validates every op output at the executor level).
    TPU-first: device code can't raise, so the check runs on fetched host
    values (no host callback in the step) — call it on
    step outputs / fetched vars. Raises EnforceError naming the bad leaves.
    """
    import jax
    import numpy as np

    bad = []
    leaves = jax.tree_util.tree_leaves_with_path(tree)
    for path, leaf in leaves:
        arr = np.asarray(leaf)
        if arr.dtype.kind == "f" and not np.all(np.isfinite(arr)):
            n_nan = int(np.isnan(arr).sum())
            n_inf = int(np.isinf(arr).sum())
            bad.append(f"{jax.tree_util.keystr(path)} "
                       f"(nan={n_nan}, inf={n_inf})")
    if bad:
        raise EnforceError(
            f"check_nan_inf: non-finite values in {label}: " + ", ".join(bad))
    return tree
