"""tracer-leak: Python control flow on traced values inside staged
functions.

A function handed to ``jax.jit`` / ``lax.scan`` / ``shard_map`` /
``pl.pallas_call`` runs once at trace time; a Python ``if``/``while``
(or ``bool()``) over one of its *traced* arguments either crashes with a
ConcretizationTypeError on first use or — worse — silently bakes the
tracing-time branch into the compiled program. The dynamic failure shows
up only when that branch is reached; this rule finds the pattern
statically, tree-wide.

Detection is deliberately conservative (a lint that cries wolf gets
disabled): a finding needs BOTH a function we can prove is staged
(``@jax.jit``-style decorator, or passed by name/lambda to a staging
call, ``functools.partial`` unwrapped, jit's literal
``static_argnums``/``static_argnames`` honored) AND a test expression
rooted at a traced parameter via truthiness — a bare
name/attribute/subscript, ``not`` of one, a ``bool()`` call, or a
boolean combination. Comparisons, ``is None`` checks, and the static
attributes (``.shape``/``.ndim``/``.dtype``/``.size``) never fire.
Taint propagates through straight-line assignments; calls like ``len``
/ ``isinstance`` and shape arithmetic stay static.
"""

import ast

from paddle_tpu.analysis.lint import Finding, Rule, register
from paddle_tpu.analysis.rules._common import (assign_name_targets,
                                               call_name, dotted_name)

# attributes of a traced array that are static python values at trace
# time — tests on them are fine
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "aval", "sharding",
                 "itemsize", "weak_type"}
# calls whose RESULT is static regardless of traced args
_STATIC_CALLS = {"len", "isinstance", "hasattr", "type", "getattr",
                 "range", "enumerate", "zip", "id", "repr", "str",
                 "format"}
# calls producing a python container: its truthiness is len-based and
# static under tracing even when the ELEMENTS are tracers (matched on
# the last dotted segment, so jax.tree_util.tree_leaves counts)
_CONTAINER_CALLS = {"tuple", "list", "set", "dict", "frozenset", "sorted",
                    "tree_leaves"}

# staging call -> (reported name, positions of the staged callables)
_STAGING_CALLS = {
    "jax.jit": ("jax.jit", (0,)), "jit": ("jax.jit", (0,)),
    "jax.pjit": ("jax.jit", (0,)), "pjit": ("jax.jit", (0,)),
    "lax.scan": ("lax.scan", (0,)), "jax.lax.scan": ("lax.scan", (0,)),
    "shard_map": ("shard_map", (0,)),
    "jax.experimental.shard_map.shard_map": ("shard_map", (0,)),
    "pl.pallas_call": ("pl.pallas_call", (0,)),
    "pallas_call": ("pl.pallas_call", (0,)),
    "lax.while_loop": ("lax.while_loop", (0, 1)),
    "jax.lax.while_loop": ("lax.while_loop", (0, 1)),
    "lax.fori_loop": ("lax.fori_loop", (2,)),
    "jax.lax.fori_loop": ("lax.fori_loop", (2,)),
    "lax.cond": ("lax.cond", (1, 2)),
    "jax.lax.cond": ("lax.cond", (1, 2)),
    "lax.map": ("lax.map", (0,)), "jax.lax.map": ("lax.map", (0,)),
    "jax.vmap": ("jax.vmap", (0,)), "vmap": ("jax.vmap", (0,)),
    "jax.grad": ("jax.grad", (0,)),
    "jax.value_and_grad": ("jax.value_and_grad", (0,)),
    "jax.checkpoint": ("jax.checkpoint", (0,)),
    "jax.remat": ("jax.checkpoint", (0,)),
}
_DECORATOR_STAGERS = {"jax.jit", "jit", "jax.pjit", "pjit",
                      "jax.checkpoint", "jax.remat"}
_PARTIAL_NAMES = {"functools.partial", "partial"}


def _static_params(call):
    """Parameter positions/names jit treats as static (literal
    static_argnums / static_argnames only)."""
    nums, names = set(), set()
    for kw in call.keywords:
        v = kw.value
        if kw.arg == "static_argnums":
            vals = v.elts if isinstance(v, (ast.Tuple, ast.List)) else [v]
            for e in vals:
                if isinstance(e, ast.Constant) and isinstance(e.value, int):
                    nums.add(e.value)
        elif kw.arg == "static_argnames":
            vals = v.elts if isinstance(v, (ast.Tuple, ast.List)) else [v]
            for e in vals:
                if isinstance(e, ast.Constant) and isinstance(e.value, str):
                    names.add(e.value)
    return nums, names


def _unwrap_partial(node):
    """partial(f, ...) -> f (one level is all the tree uses)."""
    if (isinstance(node, ast.Call)
            and call_name(node) in _PARTIAL_NAMES and node.args):
        return node.args[0]
    return node


class _TracedFn:
    def __init__(self, fn, via, static_nums=(), static_names=()):
        self.fn = fn            # FunctionDef or Lambda
        self.via = via          # 'jax.jit' / 'lax.scan' / ...
        args = fn.args
        params = [a.arg for a in args.posonlyargs + args.args]
        traced = []
        for i, p in enumerate(params):
            if p in ("self", "cls"):
                continue
            if i in static_nums or p in static_names:
                continue
            traced.append(p)
        self.traced = set(traced)


def _collect_traced(tree):
    """Every function in the module we can prove is staged."""
    # name -> def nodes (any nesting level) for by-name resolution
    defs = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs.setdefault(node.name, []).append(node)

    out = []
    seen = set()

    def _add(fn, via, nums=(), names=()):
        if id(fn) not in seen:
            seen.add(id(fn))
            out.append(_TracedFn(fn, via, nums, names))

    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if isinstance(dec, ast.Call):
                    name = call_name(dec)
                    if (name in _PARTIAL_NAMES and dec.args
                            and dotted_name(dec.args[0])
                            in _DECORATOR_STAGERS):
                        nums, names_ = _static_params(dec)
                        _add(node, "jax.jit", nums, names_)
                    elif name in _DECORATOR_STAGERS:
                        nums, names_ = _static_params(dec)
                        _add(node, "jax.jit", nums, names_)
                elif dotted_name(dec) in _DECORATOR_STAGERS:
                    _add(node, "jax.jit")
        elif isinstance(node, ast.Call):
            staged = _STAGING_CALLS.get(call_name(node))
            if staged is None:
                continue
            via, positions = staged
            nums, names = (_static_params(node)
                           if via == "jax.jit" else (set(), set()))
            for pos in positions:
                if pos >= len(node.args):
                    continue
                fn_arg = _unwrap_partial(node.args[pos])
                if isinstance(fn_arg, ast.Lambda):
                    _add(fn_arg, via, nums, names)
                elif isinstance(fn_arg, ast.Name):
                    cands = defs.get(fn_arg.id, [])
                    if len(cands) == 1:
                        _add(cands[0], via, nums, names)
    return out


class _LeakScan:
    """One staged function: propagate taint, flag truthiness tests."""

    def __init__(self, traced_fn):
        self.tf = traced_fn
        self.tainted = set(traced_fn.traced)
        self.containers = set()   # tainted names with static truthiness

    def _static_truthy(self, node):
        """Containers (and names holding them) have len-based
        truthiness, static at trace time regardless of contents."""
        if isinstance(node, ast.Name):
            return node.id in self.containers
        if isinstance(node, (ast.Tuple, ast.List, ast.Set, ast.Dict)):
            return True
        if isinstance(node, ast.Call):
            cn = call_name(node)
            return (cn is not None
                    and cn.split(".")[-1] in _CONTAINER_CALLS)
        return False

    def _rooted(self, node):
        """Is this expression's value the traced data itself (via
        names, non-static attributes, subscripts)?"""
        if self._static_truthy(node):
            return False
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        if isinstance(node, ast.Attribute):
            if node.attr in _STATIC_ATTRS:
                return False
            return self._rooted(node.value)
        if isinstance(node, ast.Subscript):
            return self._rooted(node.value)
        if isinstance(node, ast.Call):
            if call_name(node) == "bool" and node.args:
                return (not self._static_truthy(node.args[0])
                        and self._mentions_traced(node.args[0]))
            return False
        if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.Not):
            return self._rooted(node.operand)
        if isinstance(node, ast.BoolOp):
            return any(self._rooted(v) for v in node.values)
        return False

    def _mentions_traced(self, node):
        """Does the expression carry traced data (descending past
        static attrs / static calls returns False)?"""
        if isinstance(node, ast.Attribute):
            if node.attr in _STATIC_ATTRS:
                return False
            return self._mentions_traced(node.value)
        if isinstance(node, ast.Call):
            f = call_name(node)
            if f in _STATIC_CALLS:
                return False
            return any(self._mentions_traced(a) for a in node.args)
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        return any(self._mentions_traced(c) for c in ast.iter_child_nodes(node))

    def findings(self, rule, relpath):
        body = (self.tf.fn.body if isinstance(self.tf.fn.body, list)
                else [self.tf.fn.body])   # Lambda body is an expr
        # taint propagation through straight-line assignments, in
        # source order (good enough for trace-time code)
        fn_nodes = []
        for stmt in body:
            fn_nodes.extend(ast.walk(stmt))
        for node in fn_nodes:
            if isinstance(node, (ast.Assign, ast.AnnAssign)):
                value = getattr(node, "value", None)
                if value is not None and self._mentions_traced(value):
                    targets = assign_name_targets(node)
                    self.tainted.update(targets)
                    tgt_nodes = (node.targets if isinstance(node, ast.Assign)
                                 else [node.target])
                    if (len(tgt_nodes) == 1
                            and isinstance(tgt_nodes[0], ast.Name)
                            and self._static_truthy(value)):
                        self.containers.add(tgt_nodes[0].id)
                    else:
                        self.containers.difference_update(targets)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                   ast.Lambda)):
                # nested defs run at trace time too: their params carry
                # traced values when called on them
                args = node.args
                for a in args.posonlyargs + args.args:
                    if a.arg not in ("self", "cls"):
                        self.tainted.add(a.arg)

        where = getattr(self.tf.fn, "name", "<lambda>")
        for node in fn_nodes:
            if isinstance(node, (ast.If, ast.While)):
                if self._rooted(node.test):
                    kw = "while" if isinstance(node, ast.While) else "if"
                    yield Finding(
                        rule, relpath, node.lineno,
                        f"python `{kw}` on a traced value in {where} "
                        f"(staged via {self.tf.via}) — trace-time "
                        "branch on runtime data; use lax.cond/jnp.where")
            elif isinstance(node, ast.IfExp) and self._rooted(node.test):
                yield Finding(
                    rule, relpath, node.lineno,
                    f"`x if <traced> else y` in {where} (staged via "
                    f"{self.tf.via}) — trace-time branch on runtime "
                    "data; use jnp.where")
            elif (isinstance(node, ast.Call)
                  and call_name(node) == "bool" and node.args
                  and not self._static_truthy(node.args[0])
                  and self._mentions_traced(node.args[0])):
                yield Finding(
                    rule, relpath, node.lineno,
                    f"bool() on a traced value in {where} (staged via "
                    f"{self.tf.via}) — concretizes the tracer")


@register
class TracerLeak(Rule):
    name = "tracer-leak"
    help = ("python if/while/bool() over traced values inside functions "
            "staged by jax.jit / lax.scan / shard_map / pl.pallas_call")

    DEFAULT_SCOPE = ("paddle_tpu/**/*.py", "paddle_tpu/*.py",
                     "tools/*.py", "examples/*.py")

    def __init__(self, scope=None):
        self.scope = tuple(scope or self.DEFAULT_SCOPE)

    def check(self, ctx):
        for sf in ctx.glob(*self.scope):
            if sf.tree is None:
                continue
            for tf in _collect_traced(sf.tree):
                yield from _LeakScan(tf).findings(self.name, sf.relpath)
