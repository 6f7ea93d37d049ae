"""Optimizer suite — functional, pytree-based.

Ref: /root/reference/python/paddle/fluid/optimizer.py:54 (base Optimizer:
backward :488, apply_gradients :557, minimize :641) and the per-op C++
kernels in /root/reference/paddle/fluid/operators/optimizers/ (sgd_op,
momentum_op, lars_momentum_op, adam_op, adamax_op, adagrad_op,
decayed_adagrad_op, adadelta_op, rmsprop_op, ftrl_op, lamb_op, dpsgd_op).

TPU-first: an optimizer is (init(params) -> state, update per-leaf math);
the whole update fuses into the jitted train step, and under pjit the state
shards like the params. `minimize(loss_fn, params, ...)` gives the
reference's one-call API on top.
"""

import jax
import jax.numpy as jnp

from paddle_tpu.optimizer.lr_scheduler import make_schedule


def _tmap(f, *trees):
    return jax.tree_util.tree_map(f, *trees)


def _moment_slots(p, state_dtype):
    """Adam-family moment slots. moment1 stores in state_dtype (or the
    param dtype); moment2 is pinned to f32 whenever state_dtype is
    narrower than 32 bits — beta2=0.999's 1e-3 relative decay step is
    below bf16's half-ulp, so a narrow moment2 freezes at its historical
    max instead of decaying. zeros_like keeps the param's sharding."""
    m1_dt = state_dtype or p.dtype
    if state_dtype is not None and jnp.finfo(state_dtype).bits < 32:
        m2_dt = jnp.float32
    else:
        m2_dt = state_dtype or p.dtype
    return {"moment1": jnp.zeros_like(p, dtype=m1_dt),
            "moment2": jnp.zeros_like(p, dtype=m2_dt)}


class Optimizer:
    """Base (ref: optimizer.py:54). Subclasses define slots() and
    _update_leaf(g, p, slots, lr, hyper) -> (new_p, new_slots)."""

    def __init__(self, learning_rate=0.01, regularization=None,
                 grad_clip=None):
        self.lr = make_schedule(learning_rate)
        self.regularization = regularization
        self.grad_clip = grad_clip
        # bound at construction so the state pytree structure is stable for
        # this instance even if the global flag is toggled mid-run
        from paddle_tpu.core.flags import get_flag
        self._check_nan_inf = get_flag("check_nan_inf")

    # -- subclass API --
    def slots(self, p):
        """Per-param slot init: dict name -> array."""
        return {}

    def _update_leaf(self, g, p, slots, lr, step):
        raise NotImplementedError

    # -- public API --
    def init(self, params):
        state = {
            "step": jnp.zeros((), jnp.int32),
            "slots": _tmap(lambda p: self.slots(p), params,
                           ),
        }
        if self._check_nan_inf:
            # ref flags.cc:44 FLAGS_check_nan_inf. Under jit the step can't
            # raise, so bad steps are *skipped* and counted here; eager calls
            # raise EnforceError immediately (see apply_gradients).
            state["nan_inf_steps"] = jnp.zeros((), jnp.int32)
        return state

    def apply_gradients(self, params, grads, state, _decay_mask=None):
        """ref: optimizer.py apply_gradients :557 (clip → regularize →
        per-param update ops).

        With flag check_nan_inf set at construction (ref flags.cc:44): eager
        calls raise EnforceError on non-finite gradients; traced (jit) calls
        skip the whole update and increment state['nan_inf_steps'] instead,
        since device code cannot raise on TPU (and the step carries no host
        callback). The flag is bound in __init__ so the state structure
        can't change mid-run.

        _decay_mask: optional bool pytree (True = apply this optimizer's
        self.wd to the leaf) used by the decoupled-decay optimizers; kept
        inside this method so the masked path shares the nan/inf guard and
        state structure with the plain one. Mask leaves must be concrete
        (Python/np bools) — the mask picks code, not values.
        """
        check = self._check_nan_inf
        grads_in = grads
        if self.grad_clip is not None:
            grads = self.grad_clip(grads)
        if self.regularization is not None:
            grads = self.regularization(grads, params)
        if check:
            import jax.core as jcore
            leaves = [g for g in jax.tree_util.tree_leaves(grads_in)
                      if g is not None]
            finite = jnp.array(True)
            for g in leaves:
                if jnp.issubdtype(jnp.asarray(g).dtype, jnp.floating):
                    finite = finite & jnp.all(jnp.isfinite(g))
            if not isinstance(finite, jcore.Tracer) and not jnp.all(finite):
                from paddle_tpu.core.enforce import check_numerics
                check_numerics(grads_in, "gradients")
            params_in, state_in = params, state
        step = state["step"]
        lr = self.lr(step)
        flat_p, treedef = jax.tree_util.tree_flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        flat_s = treedef.flatten_up_to(state["slots"])
        if _decay_mask is None:
            flat_m = [True] * len(flat_p)
        else:
            flat_m = [bool(m) for m in treedef.flatten_up_to(_decay_mask)]
        new_p, new_s = [], []
        saved_wd = getattr(self, "wd", None)
        try:
            for g, p, s, use_decay in zip(flat_g, flat_p, flat_s, flat_m):
                if g is None:
                    new_p.append(p)
                    new_s.append(s)
                    continue
                if _decay_mask is not None:
                    self.wd = saved_wd if use_decay else 0.0
                np_, ns_ = self._update_leaf(g, p, s, lr, step)
                new_p.append(np_)
                new_s.append(ns_)
        finally:
            if _decay_mask is not None:
                self.wd = saved_wd
        params = jax.tree_util.tree_unflatten(treedef, new_p)
        slots = jax.tree_util.tree_unflatten(treedef, new_s)
        new_state = {"step": step + 1, "slots": slots}
        if check:
            # Skip the whole update on a bad step (AMP-scaler-style guard).
            keep = lambda new, old: _tmap(
                lambda a, b: jnp.where(finite, a, b), new, old)
            params = keep(params, params_in)
            new_state = keep(new_state, {k: v for k, v in state_in.items()
                                         if k != "nan_inf_steps"})
            new_state["nan_inf_steps"] = (
                state_in.get("nan_inf_steps", jnp.zeros((), jnp.int32))
                + jnp.where(finite, 0, 1))
        return params, new_state

    def _apply_gradients_decay_masked(self, params, grads, state, mask):
        """Per-leaf weight-decay masking for decoupled-decay optimizers
        (AdamW decay_mask_fn, Lamb exclude_from_weight_decay_fn). mask:
        bool pytree, True = apply this optimizer's self.wd to the leaf.
        Delegates to the base apply_gradients so the masked path keeps the
        check_nan_inf skip/count guard and the exact state structure."""
        return Optimizer.apply_gradients(self, params, grads, state,
                                         _decay_mask=mask)

    def minimize(self, loss_fn, params, state, *args, **kwargs):
        """ref: optimizer.py minimize :641 — returns
        (loss, new_params, new_state, aux)."""
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, *args, **kwargs)
        params, state = self.apply_gradients(params, grads, state)
        return loss, params, state, aux


class SGD(Optimizer):
    """ref: operators/optimizers/sgd_op.cc"""

    def _update_leaf(self, g, p, s, lr, step):
        return p - lr * g.astype(p.dtype), s


class Momentum(Optimizer):
    """ref: operators/optimizers/momentum_op.h (velocity = mu*v + g;
    p -= lr * (g + mu*v) if nesterov else lr*v).

    state_dtype: storage dtype for the velocity slot (default: param
    dtype). bf16 velocity halves the optimizer's HBM traffic — for
    HBM-bound models (ResNet-50: ~100 MB of f32 velocity r+w per step)
    that is ~1 ms/step on v5e at the cost of ~3 decimal digits on a
    quantity that is itself a lossy running average. Update math runs in
    the WIDER of (param, state) dtype, so f32 state over bf16 params is
    a true master velocity."""

    def __init__(self, learning_rate=0.01, momentum=0.9, use_nesterov=False,
                 state_dtype=None, **kw):
        super().__init__(learning_rate, **kw)
        self.mu = momentum
        self.nesterov = use_nesterov
        self.state_dtype = state_dtype

    def slots(self, p):
        # zeros_like keeps the param's sharding for the slot (pjit init)
        dt = self.state_dtype or p.dtype
        return {"velocity": jnp.zeros_like(p, dtype=dt)}

    def _update_leaf(self, g, p, s, lr, step):
        # compute in the WIDER of (param, state) dtype so an f32
        # state_dtype over bf16 params acts as a true master velocity,
        # not f32 storage of a bf16-computed value
        cd = jnp.promote_types(p.dtype, s["velocity"].dtype)
        g = g.astype(cd)
        v = self.mu * s["velocity"].astype(cd) + g
        if self.nesterov:
            p = (p.astype(cd) - lr * (g + self.mu * v)).astype(p.dtype)
        else:
            p = (p.astype(cd) - lr * v).astype(p.dtype)
        # single source of truth for storage dtype: whatever slots() chose
        return p, {"velocity": v.astype(s["velocity"].dtype)}


class LarsMomentum(Optimizer):
    """LARS (ref: operators/optimizers/lars_momentum_op.cc): layer-wise
    adaptive rate = lr * coeff * ||p|| / (||g|| + lambda*||p||)."""

    def __init__(self, learning_rate=0.01, momentum=0.9, lars_coeff=1e-3,
                 lars_weight_decay=5e-4, epsilon=1e-9, **kw):
        super().__init__(learning_rate, **kw)
        self.mu = momentum
        self.coeff = lars_coeff
        self.wd = lars_weight_decay
        self.eps = epsilon

    def slots(self, p):
        return {"velocity": jnp.zeros_like(p)}

    def _update_leaf(self, g, p, s, lr, step):
        g = g.astype(jnp.float32)
        pf = p.astype(jnp.float32)
        pn = jnp.sqrt(jnp.sum(jnp.square(pf)))
        gn = jnp.sqrt(jnp.sum(jnp.square(g)))
        local = self.coeff * pn / (gn + self.wd * pn + self.eps)
        local = jnp.where(pn > 0, local, 1.0)
        v = self.mu * s["velocity"] + lr * local * (g + self.wd * pf)
        return (pf - v).astype(p.dtype), {"velocity": v}


class Adagrad(Optimizer):
    """ref: operators/optimizers/adagrad_op.cc"""

    def __init__(self, learning_rate=0.01, epsilon=1e-6, initial_accumulator_value=0.0, **kw):
        super().__init__(learning_rate, **kw)
        self.eps = epsilon
        self.init_acc = initial_accumulator_value

    def slots(self, p):
        return {"moment": jnp.full_like(p, self.init_acc)}

    def _update_leaf(self, g, p, s, lr, step):
        g = g.astype(p.dtype)
        m = s["moment"] + jnp.square(g)
        p = p - lr * g / (jnp.sqrt(m) + self.eps)
        return p, {"moment": m}


class DecayedAdagrad(Optimizer):
    """ref: operators/optimizers/decayed_adagrad_op.cc"""

    def __init__(self, learning_rate=0.01, decay=0.95, epsilon=1e-6, **kw):
        super().__init__(learning_rate, **kw)
        self.decay, self.eps = decay, epsilon

    def slots(self, p):
        return {"moment": jnp.zeros_like(p)}

    def _update_leaf(self, g, p, s, lr, step):
        g = g.astype(p.dtype)
        m = self.decay * s["moment"] + (1 - self.decay) * jnp.square(g)
        return p - lr * g / (jnp.sqrt(m) + self.eps), {"moment": m}


class Adadelta(Optimizer):
    """ref: operators/optimizers/adadelta_op.cc"""

    def __init__(self, learning_rate=1.0, epsilon=1e-6, rho=0.95, **kw):
        super().__init__(learning_rate, **kw)
        self.eps, self.rho = epsilon, rho

    def slots(self, p):
        return {"avg_squared_grad": jnp.zeros_like(p),
                "avg_squared_update": jnp.zeros_like(p)}

    def _update_leaf(self, g, p, s, lr, step):
        g = g.astype(p.dtype)
        asg = self.rho * s["avg_squared_grad"] + (1 - self.rho) * jnp.square(g)
        upd = g * jnp.sqrt(s["avg_squared_update"] + self.eps) / \
            jnp.sqrt(asg + self.eps)
        asu = self.rho * s["avg_squared_update"] + (1 - self.rho) * jnp.square(upd)
        return p - lr * upd, {"avg_squared_grad": asg,
                              "avg_squared_update": asu}


class RMSProp(Optimizer):
    """ref: operators/optimizers/rmsprop_op.cc (centered + momentum variants)."""

    def __init__(self, learning_rate=0.01, rho=0.95, epsilon=1e-6,
                 momentum=0.0, centered=False, **kw):
        super().__init__(learning_rate, **kw)
        self.rho, self.eps, self.mu, self.centered = rho, epsilon, momentum, centered

    def slots(self, p):
        s = {"mean_square": jnp.zeros_like(p), "moment": jnp.zeros_like(p)}
        if self.centered:
            s["mean_grad"] = jnp.zeros_like(p)
        return s

    def _update_leaf(self, g, p, s, lr, step):
        g = g.astype(p.dtype)
        ms = self.rho * s["mean_square"] + (1 - self.rho) * jnp.square(g)
        out = {"mean_square": ms}
        if self.centered:
            mg = self.rho * s["mean_grad"] + (1 - self.rho) * g
            denom = jnp.sqrt(ms - jnp.square(mg) + self.eps)
            out["mean_grad"] = mg
        else:
            denom = jnp.sqrt(ms + self.eps)
        mom = self.mu * s["moment"] + lr * g / denom
        out["moment"] = mom
        return p - mom, out


class Adam(Optimizer):
    """ref: operators/optimizers/adam_op.h — bias-corrected.

    state_dtype: storage dtype for the moment1 slot (default: param
    dtype). bf16 moment1 cuts the optimizer-state traffic by a quarter
    (BERT-base Adam: ~880 MB of f32 moments r+w per step on v5e).
    moment2 is PINNED to f32 whenever state_dtype is narrower than 32
    bits: with beta2=0.999 the per-step relative decay (1e-3) is below
    bf16's half-ulp (~2e-3), so a bf16 moment2 can never decay — it
    freezes at its historical max and permanently suppresses the
    effective lr. moment1's 1-beta1=0.1 step is safely representable.
    Update math always runs in f32; slot dtypes apply at store time.

    lazy_mode is accepted for reference API compatibility but is a
    documented no-op: it exists in the reference to restrict updates to
    rows touched by sparse (SelectedRows) gradients, and the TPU-first
    sparse-row path here is `parallel.sparse.SparseTable` pull/push with
    its own per-row optimizer, so dense Adam never sees row-sparse
    gradients."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, lazy_mode=False, state_dtype=None, **kw):
        super().__init__(learning_rate, **kw)
        self.b1, self.b2, self.eps = beta1, beta2, epsilon
        self.state_dtype = state_dtype

    def slots(self, p):
        return _moment_slots(p, self.state_dtype)

    def _update_leaf(self, g, p, s, lr, step):
        g = g.astype(jnp.float32)
        t = (step + 1).astype(jnp.float32)
        m = self.b1 * s["moment1"].astype(jnp.float32) + (1 - self.b1) * g
        v = self.b2 * s["moment2"].astype(jnp.float32) \
            + (1 - self.b2) * jnp.square(g)
        mhat = m / (1 - self.b1 ** t)
        vhat = v / (1 - self.b2 ** t)
        new_p = p.astype(jnp.float32) - lr * mhat / (jnp.sqrt(vhat) + self.eps)
        # store in the slot dtype slots() chose (also keeps the state
        # pytree dtype-stable across steps when params are not f32)
        return new_p.astype(p.dtype), {
            "moment1": m.astype(s["moment1"].dtype),
            "moment2": v.astype(s["moment2"].dtype)}


class AdamW(Adam):
    """Decoupled weight decay (modern; reference era used L2 regularizer)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, weight_decay=0.01, decay_mask_fn=None, **kw):
        super().__init__(learning_rate, beta1, beta2, epsilon, **kw)
        self.wd = weight_decay
        self.decay_mask_fn = decay_mask_fn

    def _update_leaf(self, g, p, s, lr, step):
        new_p, slots = super()._update_leaf(g, p, s, lr, step)
        decay = self.wd
        if decay:
            new_p = new_p - lr * decay * p
        return new_p, slots

    def apply_gradients(self, params, grads, state):
        if self.decay_mask_fn is not None:
            mask = self.decay_mask_fn(params)
            return self._apply_gradients_decay_masked(
                params, grads, state, mask)
        return super().apply_gradients(params, grads, state)


class Adamax(Optimizer):
    """ref: operators/optimizers/adamax_op.h"""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, **kw):
        super().__init__(learning_rate, **kw)
        self.b1, self.b2, self.eps = beta1, beta2, epsilon

    def slots(self, p):
        return {"moment": jnp.zeros_like(p), "inf_norm": jnp.zeros_like(p)}

    def _update_leaf(self, g, p, s, lr, step):
        g = g.astype(p.dtype)
        t = (step + 1).astype(jnp.float32)
        m = self.b1 * s["moment"] + (1 - self.b1) * g
        u = jnp.maximum(self.b2 * s["inf_norm"], jnp.abs(g))
        p = p - (lr / (1 - self.b1 ** t)) * m / (u + self.eps)
        return p, {"moment": m, "inf_norm": u}


class Ftrl(Optimizer):
    """ref: operators/optimizers/ftrl_op.h"""

    def __init__(self, learning_rate=0.01, l1=0.0, l2=0.0, lr_power=-0.5,
                 **kw):
        super().__init__(learning_rate, **kw)
        self.l1, self.l2, self.lr_power = l1, l2, lr_power

    def slots(self, p):
        return {"squared": jnp.zeros_like(p), "linear": jnp.zeros_like(p)}

    def _update_leaf(self, g, p, s, lr, step):
        g = g.astype(p.dtype)
        new_sq = s["squared"] + jnp.square(g)
        lp = -self.lr_power
        sigma = (jnp.power(new_sq, lp) - jnp.power(s["squared"], lp)) / lr
        lin = s["linear"] + g - sigma * p
        quad = jnp.power(new_sq, lp) / lr + 2 * self.l2
        pre = -lin + jnp.sign(lin) * self.l1
        p = jnp.where(jnp.abs(lin) > self.l1, pre / quad, 0.0)
        return p, {"squared": new_sq, "linear": lin}


class Lamb(Optimizer):
    """ref: operators/optimizers/lamb_op.h — layer-wise adaptation for large
    batch (BERT-scale). state_dtype: same reduced-precision moment1
    storage as Adam (f32 math, slot-dtype store, f32-pinned moment2).
    exclude_from_weight_decay_fn(params) -> bool pytree, True = exclude
    that leaf from weight decay (the BERT recipe excludes LayerNorm
    scales and biases)."""

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01,
                 beta1=0.9, beta2=0.999, epsilon=1e-6,
                 exclude_from_weight_decay_fn=None, state_dtype=None, **kw):
        super().__init__(learning_rate, **kw)
        self.wd = lamb_weight_decay
        self.b1, self.b2, self.eps = beta1, beta2, epsilon
        self.exclude_fn = exclude_from_weight_decay_fn
        self.state_dtype = state_dtype

    def slots(self, p):
        return _moment_slots(p, self.state_dtype)

    def apply_gradients(self, params, grads, state):
        if self.exclude_fn is not None:
            excl = self.exclude_fn(params)
            mask = jax.tree_util.tree_map(lambda e: not bool(e), excl)
            return self._apply_gradients_decay_masked(
                params, grads, state, mask)
        return super().apply_gradients(params, grads, state)

    def _update_leaf(self, g, p, s, lr, step):
        g = g.astype(jnp.float32)
        pf = p.astype(jnp.float32)
        t = (step + 1).astype(jnp.float32)
        m = self.b1 * s["moment1"].astype(jnp.float32) + (1 - self.b1) * g
        v = self.b2 * s["moment2"].astype(jnp.float32) \
            + (1 - self.b2) * jnp.square(g)
        mhat = m / (1 - self.b1 ** t)
        vhat = v / (1 - self.b2 ** t)
        r = mhat / (jnp.sqrt(vhat) + self.eps) + self.wd * pf
        pn = jnp.sqrt(jnp.sum(jnp.square(pf)))
        rn = jnp.sqrt(jnp.sum(jnp.square(r)))
        trust = jnp.where((pn > 0) & (rn > 0), pn / rn, 1.0)
        return (pf - lr * trust * r).astype(p.dtype), \
            {"moment1": m.astype(s["moment1"].dtype),
             "moment2": v.astype(s["moment2"].dtype)}


class Dpsgd(Optimizer):
    """Differentially-private SGD (ref: operators/optimizers/dpsgd_op.cc):
    clip per-update + Gaussian noise."""

    def __init__(self, learning_rate=0.01, clip=10.0, batch_size=16.0,
                 sigma=1.0, seed=0, **kw):
        super().__init__(learning_rate, **kw)
        self.clip_v, self.batch_size, self.sigma = clip, batch_size, sigma
        self.seed = seed

    def slots(self, p):
        return {}

    def apply_gradients(self, params, grads, state):
        # reset the trace-time leaf counter so each parameter draws
        # INDEPENDENT noise (leaf order is fixed by the treedef)
        self._leaf_idx = 0
        return super().apply_gradients(params, grads, state)

    def _update_leaf(self, g, p, s, lr, step):
        g = g.astype(p.dtype)
        leaf_idx = getattr(self, "_leaf_idx", 0)
        self._leaf_idx = leaf_idx + 1
        key = jax.random.fold_in(jax.random.key(self.seed), step)
        key = jax.random.fold_in(key, leaf_idx)
        gn = jnp.sqrt(jnp.sum(jnp.square(g)))
        g = g * jnp.minimum(1.0, self.clip_v / jnp.maximum(gn, 1e-12))
        noise = self.sigma * self.clip_v / self.batch_size * \
            jax.random.normal(key, g.shape, g.dtype)
        return p - lr * (g + noise), s
