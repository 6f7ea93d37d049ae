"""The training window: ``Trainer.train`` over the model's ``.loss()``
with the amp-decorated Adam step, one AOT-compiled program.

Set-up builds ONE object (``StepDriver``: the compiled step, its state
and the feed), drives it through its first three steps through the same
``Trainer.train`` call and the same feed as the window, keeps what the
comparison needs (each step's loss, the first gradient's norms as Adam
got it, the parameters' change), and hands that same object to the
window. The plain reference follows those three steps after the window
has closed and the program's state is freed.
"""

import gc
import importlib
import time

import numpy as np

from benchmark.harness import compare, weights


class Feed:
    """Batches from the seed: step ``i`` of seed ``s`` is always the same
    batch, whoever asks and whenever."""

    def __init__(self, task, model_cfg, traffic, seed):
        self.task, self.model_cfg, self.traffic = task, model_cfg, traffic
        self.seed = int(seed)
        self.step = 0

    def batch(self, step):
        rng = np.random.default_rng([self.seed, step])
        return self.task.make_batch(rng, self.model_cfg, self.traffic)

    def reader(self, n=None, until=None, clock=time.perf_counter):
        """A Trainer dataset: ``n`` batches, or batches until the clock
        passes ``until``."""
        def gen():
            made = 0
            while ((n is None or made < n)
                   and (until is None or clock() < until)):
                yield self.batch(self.step)
                self.step += 1
                made += 1
        return gen


class StepDriver:
    """The compiled step with its state. Called by the Trainer as its
    step function; keeps every loss (a device scalar, no sync) and lets
    at most ``max_in_flight`` steps run ahead of the host, so that the
    window closes soon after its deadline."""

    def __init__(self, exe, state, max_in_flight):
        self.exe, self.state = exe, state
        self.max_in_flight = max_in_flight
        self.losses = []

    def __call__(self, state, *batch):
        loss, state = self.exe(state, *batch)
        self.losses.append(loss)
        k = len(self.losses) - 1 - self.max_in_flight
        if k >= 0:
            self.losses[k].block_until_ready()
        return loss, state

    def train(self, trainer, dataset):
        self.state, stats = trainer.train(self.state, dataset)
        return stats


def build(ctx):
    """Everything up to the first step: model, weights from the seed on
    the device, optimizer state, the compiled step, the Trainer."""
    import jax

    import paddle_tpu as pt
    from paddle_tpu.static.trainer import Trainer, TrainerConfig

    config, traffic = ctx["config"], ctx["traffic"]
    task = importlib.import_module(traffic["task"])
    model, params = weights.model_and_params(config, ctx["seed"])
    o = traffic["optimizer"]
    opt = pt.amp.decorate(
        pt.optimizer.Adam(o["lr"], beta1=o["beta1"], beta2=o["beta2"],
                          epsilon=o["eps"]), pt.amp.bf16_policy())
    # one program for the optimizer's state, not one per leaf
    state = {"params": params, "opt": jax.jit(opt.init)(params)}
    jax.block_until_ready(state)
    ctx["log"]("weights and optimizer state on the device")
    loss_fn = task.bind_loss(model)

    def train_step(state, *batch):
        loss, params, opt_state, _ = opt.minimize(
            loss_fn, state["params"], state["opt"], *batch)
        return loss, {"params": params, "opt": opt_state}

    feed = Feed(task, config["shapes"], traffic, ctx["seed"])
    first = tuple(jax.device_put(a) for a in feed.batch(0))
    t0 = time.perf_counter()
    exe = jax.jit(train_step, donate_argnums=(0,)).lower(
        state, *first).compile()
    ctx["log"](f"step program ready in {time.perf_counter() - t0:.2f} s")
    driver = StepDriver(exe, state, traffic["max_in_flight"])
    trainer = Trainer(driver, TrainerConfig(
        num_ingest_threads=traffic["ingest_threads"],
        channel_capacity=traffic["channel_capacity"], prefetch=True))
    return driver, trainer, feed, task


def first_steps(driver, trainer, feed, beta1, n_steps=3):
    """Drive the first ``n_steps`` through the window's own call and
    feed; returns the program's side of the comparison."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def copy(tree):
        return jax.tree_util.tree_map(jnp.copy, tree)

    @jax.jit
    def grad_norms(params, slots):
        return jax.tree_util.tree_map(
            lambda p, s: jnp.sqrt(jnp.sum(jnp.square(
                s["moment1"].astype(jnp.float32)))) / (1.0 - beta1),
            params, slots)

    @jax.jit
    def diff_norms(a, b):
        return jax.tree_util.tree_map(
            lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
                x.astype(jnp.float32) - y.astype(jnp.float32)))), a, b)

    p0 = copy(driver.state["params"])
    driver.train(trainer, feed.reader(n=1))
    # Adam's first moment after one step is (1 - beta1) * g: the gradient
    # as the optimizer got it, read from the state it left
    g = jax.device_get(grad_norms(driver.state["params"],
                                  driver.state["opt"]["inner"]["slots"]))
    driver.train(trainer, feed.reader(n=n_steps - 1))
    u = jax.device_get(diff_norms(driver.state["params"], p0))
    del p0
    return {"losses": [float(x) for x in driver.losses[:n_steps]],
            "grad_norms": g, "update_norms": u}


def reference_steps(ctx, task, n_steps=3, precision="highest", rows=None):
    """The plain reference over the same first steps, from the seed.
    ``rows`` keeps only the first rows of each batch (a planted fault)."""
    import jax

    config, traffic = ctx["config"], ctx["traffic"]
    ref = importlib.import_module(task.REFERENCE)
    _, params = weights.model_and_params(config, ctx["seed"])
    feed = Feed(task, config["shapes"], traffic, ctx["seed"])
    batches = [tuple(jax.device_put(a[:rows]) for a in feed.batch(i))
               for i in range(n_steps)]
    return ref.train_steps(
        params, batches, num_heads=config["shapes"]["num_heads"],
        optimizer=traffic["optimizer"], precision=precision,
        block_rows=traffic.get("reference_block_rows"))


def run(ctx):
    import jax

    from paddle_tpu.observability import metrics as registry

    traffic, log = ctx["traffic"], ctx["log"]
    driver, trainer, feed, task = build(ctx)
    got = first_steps(driver, trainer, feed, traffic["optimizer"]["beta1"])
    log(f"first losses {got['losses']}")

    seconds = ctx["seconds"]
    if ctx["trace"]:
        seconds = min(seconds, traffic["trace_seconds"])
    stall = registry.counter("trainer.ingest_stall_s")
    stall0, steps0 = stall.total(), len(driver.losses)
    built0 = ctx["compiles"].compiles
    tracer = ctx["tracer"]
    if ctx["trace"]:
        tracer.start()
    t_open = time.perf_counter()
    ctx["setup_s"] = t_open - ctx["t_process"]
    driver.train(trainer, feed.reader(until=t_open + seconds))
    jax.block_until_ready(driver.state)
    t_close = time.perf_counter()
    if ctx["trace"]:
        tracer.stop()
    window = t_close - t_open
    steps = len(driver.losses) - steps0
    built = ctx["compiles"].compiles - built0
    tokens = steps * task.tokens_per_step(traffic)
    last = float(driver.losses[-1])
    log(f"window {window:.3f} s, {steps} steps, last loss {last:.4f}, "
        f"programs built inside the window: {built}")

    device = ctx["describe"]()
    # free the program's state before the reference runs
    driver.state = driver.exe = None
    del trainer
    gc.collect()

    ref = reference_steps(ctx, task)
    numbers, notes = compare.train_numbers(got, ref)
    numbers["last_loss_finite"] = 0.0 if np.isfinite(last) else 1.0
    log(f"reference losses {ref['losses']}; worst leaves {notes}")
    return {
        "attempted": steps, "failed": 0, "window_s": window,
        "programs_built_in_window": built,
        "e2e": {"train_tokens_per_s": tokens / window},
        "numbers": numbers, "device": device,
        "facts": {"steps": steps, "tokens": tokens, "window_s": window,
                  "t_open": t_open, "t_close": t_close,
                  "ingest_stall_s": stall.total() - stall0,
                  "tokens_per_step": task.tokens_per_step(traffic)},
    }


def control(ctx):
    """The readings a limit is set from, at the cell's own size, for one
    seed: the program against the reference; the CONTROL (the reference
    in int8, put in the program's place) against the reference; and the
    fault 'half of the batch left out, the mean taken over the rest',
    planted in the reference put in the program's place. 'A step that
    returns its state unchanged' reads update_gap = 1 by the measure and
    needs no run."""
    traffic = ctx["traffic"]
    driver, trainer, feed, task = build(ctx)
    got = first_steps(driver, trainer, feed, traffic["optimizer"]["beta1"])
    driver.state = driver.exe = None
    del trainer
    gc.collect()
    ref = reference_steps(ctx, task)
    out = {"program": compare.train_numbers(got, ref)[0]}
    low = reference_steps(ctx, task, precision=ctx["control_precision"])
    out["control"] = compare.train_numbers(low, ref)[0]
    half = reference_steps(ctx, task, rows=traffic["batch"] // 2)
    out["fault_half_batch"] = compare.train_numbers(half, ref)[0]
    out["losses"] = {"program": got["losses"], "reference": ref["losses"],
                     "control": low["losses"]}
    return out
