"""hot-path-sync: no device synchronization reachable from the serving
or training hot loops.

The "no sync on the hot path" invariant was previously enforced only
dynamically, by flush-spy tests covering two call sites. This rule makes
it static and whole-tree: build the call graph over the hot-path module
set, walk everything reachable from ``ServingEngine.submit/step/drain``
and ``Trainer.train``, and flag the synchronizing primitives —
``.block_until_ready()``, ``jax.device_get(...)``, ``.item()``, and
``np.asarray``/``np.array`` applied to a *device* value (a result of a
``jax.jit``-built callable, tracked by a light per-function taint pass;
``np.asarray`` over host lists/prompts is staging, not syncing, and is
deliberately not flagged).

Deliberate syncs (the scheduler consuming this step's sampled tokens,
telemetry's trailing loss fetch) stay in the tree under
``# graft-lint: disable=hot-path-sync (<why>)`` — the rule's job is to
make every *new* sync a reviewed decision, not to pretend zero exist.

Call resolution (shared with the concurrency rules via
``rules/callgraph.py``), in order: ``self.m()`` to the same class; bare
``f()`` to the module (or a ``from paddle_tpu.x import f`` target
inside the module set); ``obj.m()`` to ``Cls.m`` when exactly one
analyzed class defines ``m`` (ambiguous names are skipped, never
guessed). Nested defs are analyzed as part of their enclosing function.
"""

import ast

from paddle_tpu.analysis.lint import Finding, Rule, register
from paddle_tpu.analysis.rules import callgraph
from paddle_tpu.analysis.rules._common import (assign_name_targets,
                                               call_name)

_NP_ROOTS = {"np", "numpy"}


@register
class HotPathSync(Rule):
    name = "hot-path-sync"
    help = ("block_until_ready / jax.device_get / .item() / np.asarray-"
            "on-device reachable from ServingEngine.submit/step or the "
            "Trainer step loop")

    DEFAULT_MODULES = (
        "paddle_tpu/serving/engine.py",
        "paddle_tpu/static/trainer.py",
        "paddle_tpu/static/guardian.py",
        "paddle_tpu/observability/telemetry.py",
        "paddle_tpu/observability/watchdog.py",
        "paddle_tpu/observability/trace.py",
        "paddle_tpu/observability/spans.py",
        "paddle_tpu/observability/flight.py",
        "paddle_tpu/data/loader.py",
    )
    DEFAULT_ROOTS = (
        ("paddle_tpu/serving/engine.py", "ServingEngine.submit"),
        ("paddle_tpu/serving/engine.py", "ServingEngine.step"),
        ("paddle_tpu/serving/engine.py", "ServingEngine.drain"),
        ("paddle_tpu/static/trainer.py", "Trainer.train"),
    )

    def __init__(self, modules=None, roots=None):
        self.module_paths = tuple(modules or self.DEFAULT_MODULES)
        self.roots = tuple(roots or self.DEFAULT_ROOTS)

    # --- call graph (built by rules/callgraph.py, PR 8 semantics) ---

    def _index(self, ctx):
        return callgraph.build_index(ctx, self.module_paths)

    def _edges(self, mods, method_owner, rel, qualname):
        """(relpath, qualname) call targets of one function body."""
        yield from callgraph.call_edges(mods, method_owner, rel, qualname)

    # --- device-value taint + sync detection inside one function ---

    @staticmethod
    def _mentions(node, names):
        for sub in ast.walk(node):
            if isinstance(sub, ast.Name) and sub.id in names:
                return True
        return False

    def _device_names(self, mod, qualname, fn):
        """Local names bound (possibly via unpack) to results of jitted
        callables: self.<jitted attr>(...), a local jax.jit(...) value,
        or an expression that mentions an already-tainted name."""
        cls = qualname.split(".")[0] if "." in qualname else None
        jitted_attrs = mod.jitted_attrs.get(cls, set())
        local_jits = set()
        tainted = set()

        def _device_call(call):
            f = call.func
            if (isinstance(f, ast.Attribute)
                    and isinstance(f.value, ast.Name)
                    and f.value.id == "self" and f.attr in jitted_attrs):
                return True
            return isinstance(f, ast.Name) and f.id in local_jits

        for node in ast.walk(fn):
            if not isinstance(node, (ast.Assign, ast.AnnAssign)):
                continue
            value = getattr(node, "value", None)
            if value is None:
                continue
            targets = assign_name_targets(node)
            if isinstance(value, ast.Call) and callgraph.is_jit_call(value):
                local_jits.update(targets)
                continue
            taint = ((isinstance(value, ast.Call) and _device_call(value))
                     or self._mentions(value, tainted))
            if not taint:
                for sub in ast.walk(value):
                    if isinstance(sub, ast.Call) and _device_call(sub):
                        taint = True
                        break
            if taint:
                tainted.update(targets)
        return tainted

    def _sync_findings(self, mod, rel, qualname, root_desc):
        fn = mod.functions.get(qualname)
        if fn is None:
            return
        device = self._device_names(mod, qualname, fn)
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            name = call_name(node)
            if isinstance(f, ast.Attribute) and f.attr == "block_until_ready":
                yield Finding(
                    self.name, rel, node.lineno,
                    f".block_until_ready() in {qualname} — device sync "
                    f"reachable from {root_desc}")
            elif name in ("jax.device_get", "device_get"):
                yield Finding(
                    self.name, rel, node.lineno,
                    f"jax.device_get in {qualname} — device fetch "
                    f"reachable from {root_desc}")
            elif (isinstance(f, ast.Attribute) and f.attr == "item"
                  and not node.args and not node.keywords):
                yield Finding(
                    self.name, rel, node.lineno,
                    f".item() in {qualname} — scalar device fetch "
                    f"reachable from {root_desc}")
            elif (name is not None and "." in name
                  and name.split(".")[0] in _NP_ROOTS
                  and name.split(".")[-1] in ("asarray", "array")
                  and any(self._mentions(a, device) for a in node.args)):
                yield Finding(
                    self.name, rel, node.lineno,
                    f"{name} over a jitted-call result in {qualname} — "
                    f"host sync reachable from {root_desc}")

    def check(self, ctx):
        mods, method_owner = self._index(ctx)
        seen = set()
        queue = []
        for rel, qn in self.roots:
            mod = mods.get(rel)
            if mod is None or qn not in mod.functions:
                yield Finding(
                    self.name, rel, 1,
                    f"hot-path root {qn!r} not found — the rule's root "
                    "list rotted; update HotPathSync.DEFAULT_ROOTS")
                continue
            queue.append((rel, qn, qn))
            seen.add((rel, qn))
        while queue:
            rel, qn, root = queue.pop()
            yield from self._sync_findings(mods[rel], rel, qn, root)
            for tgt in self._edges(mods, method_owner, rel, qn):
                if tgt not in seen:
                    seen.add(tgt)
                    queue.append((tgt[0], tgt[1], root))
