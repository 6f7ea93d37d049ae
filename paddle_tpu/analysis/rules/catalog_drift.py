"""catalog-drift: every literal metric call site is cataloged, with the
cataloged kind.

The AST port of tests/test_catalog.py's regex lint: each
``.counter("x")`` / ``.gauge("x")`` / ``.histogram("x")`` call with a
literal first argument in the framework source must name a metric in
``observability/catalog.py``'s CATALOG (exact match, or a registered
``"family."`` prefix), declared with the same kind — so the exporter's
HELP lines, dashboards, and alert rules never chase a renamed or ad-hoc
metric. The catalog itself is parsed statically (dict literal of
``MetricSpec(kind, ...)``), keeping the rule importable without jax.
"""

import ast

from paddle_tpu.analysis.lint import Finding, Rule, register
from paddle_tpu.analysis.rules._common import (call_name, str_arg,
                                               walk_calls)

_KINDS = ("counter", "gauge", "histogram")


def parse_catalog(sf):
    """{metric name: kind} from a catalog module's CATALOG literal."""
    if sf is None or sf.tree is None:
        return None
    for node in sf.tree.body:
        if not (isinstance(node, ast.Assign)
                and any(isinstance(t, ast.Name) and t.id == "CATALOG"
                        for t in node.targets)
                and isinstance(node.value, ast.Dict)):
            continue
        catalog = {}
        for k, v in zip(node.value.keys, node.value.values):
            if not (isinstance(k, ast.Constant)
                    and isinstance(k.value, str)):
                continue
            kind = None
            if isinstance(v, ast.Call):
                kind = str_arg(v)
                if kind is None:
                    for kw in v.keywords:
                        if (kw.arg == "kind"
                                and isinstance(kw.value, ast.Constant)):
                            kind = kw.value.value
            if isinstance(kind, str):
                catalog[k.value] = kind
        return catalog
    return None


def lookup(catalog, name):
    """catalog.lookup semantics: exact, else longest '.'-prefix."""
    if name in catalog:
        return catalog[name]
    best = None
    for key, kind in catalog.items():
        if key.endswith(".") and name.startswith(key):
            if best is None or len(key) > len(best[0]):
                best = (key, kind)
    return best[1] if best else None


@register
class CatalogDrift(Rule):
    name = "catalog-drift"
    help = ("literal .counter()/.gauge()/.histogram() call sites must "
            "be in observability/catalog.py CATALOG with that kind")

    DEFAULT_CATALOG_PATH = "paddle_tpu/observability/catalog.py"
    DEFAULT_SCOPE = ("paddle_tpu/**/*.py", "paddle_tpu/*.py",
                     "tools/*.py")
    # below this many sites the detection itself has rotted (the tree
    # holds ~40 wired metric call sites today)
    MIN_SITES = 25

    def __init__(self, catalog_path=None, scope=None, min_sites=None):
        self.catalog_path = catalog_path or self.DEFAULT_CATALOG_PATH
        self.scope = tuple(scope or self.DEFAULT_SCOPE)
        self.min_sites = (self.MIN_SITES if min_sites is None
                          else min_sites)

    def sites(self, ctx):
        """Every literal metric call site: (sf, lineno, kind, name)."""
        out = []
        for sf in ctx.glob(*self.scope):
            if sf.tree is None or sf.relpath == self.catalog_path:
                continue
            for call in walk_calls(sf.tree):
                f = call.func
                if not (isinstance(f, ast.Attribute) and f.attr in _KINDS):
                    continue
                name = str_arg(call)
                if name is not None:
                    out.append((sf, call.lineno, f.attr, name))
        return out

    def check(self, ctx):
        catalog = parse_catalog(ctx.file(self.catalog_path))
        if catalog is None:
            yield Finding(self.name, self.catalog_path, 1,
                          "CATALOG dict literal not found — the rule's "
                          "anchor rotted")
            return
        sites = self.sites(ctx)
        if len(sites) < self.min_sites:
            yield Finding(
                self.name, self.catalog_path, 1,
                f"only {len(sites)} metric call sites detected (expected "
                f">= {self.min_sites}) — the site detection rotted")
        for sf, lineno, kind, name in sites:
            cataloged = lookup(catalog, name)
            if cataloged is None:
                yield Finding(
                    self.name, sf.relpath, lineno,
                    f"{kind}({name!r}) is not in "
                    "observability/catalog.py CATALOG")
            elif cataloged != kind:
                yield Finding(
                    self.name, sf.relpath, lineno,
                    f"{name!r} called as {kind} but cataloged as "
                    f"{cataloged}")
