"""flag-drift: core/flags.py, the README flag table, and every literal
flag read must agree — in all directions.

Three checks:

* every ``define_flag("name", ...)`` appears in a README flag table
  (a markdown table whose header's first cell is ``Flag``);
* every backticked lowercase token in a flag-table row's first cell
  names a defined flag (so the README can't advertise a knob that
  doesn't exist — spell non-flag knobs like ``cfg.scan_layers`` with
  their dotted owner to keep them out of the flag namespace);
* every literal ``get_flag("x")`` call and every literal key of a
  ``set_flags({...})`` dict names a defined flag.

Flags resolved dynamically (``get_flag(name)``) are out of static
reach and deliberately skipped.
"""

import ast
import re

from paddle_tpu.analysis.lint import Finding, Rule, register
from paddle_tpu.analysis.rules._common import (call_name, str_arg,
                                               walk_calls)

# a backticked flag token: lowercase snake_case only, so env spellings
# (PT_FLAGS_x) and dotted config knobs (cfg.scan_layers) never register
_FLAG_TOKEN = re.compile(r"`([a-z][a-z0-9_]*)`")


def _table_rows(lines):
    """(lineno, first_cell) for data rows of every markdown table whose
    header row's first cell is exactly 'Flag'."""
    in_flag_table = False
    for i, line in enumerate(lines, 1):
        stripped = line.strip()
        if not stripped.startswith("|"):
            in_flag_table = False
            continue
        cells = [c.strip() for c in stripped.strip("|").split("|")]
        if not cells:
            continue
        if cells[0] == "Flag":
            in_flag_table = True
            continue
        if set(cells[0]) <= {"-", ":", " "}:
            continue                      # the |---|---| separator
        if in_flag_table:
            yield i, cells[0]


@register
class FlagDrift(Rule):
    name = "flag-drift"
    help = ("core/flags.py definitions, the README flag table, and "
            "literal get_flag()/set_flags() sites must agree both ways")

    DEFAULT_FLAGS_PATH = "paddle_tpu/core/flags.py"
    DEFAULT_README_PATH = "README.md"
    DEFAULT_SCOPE = ("paddle_tpu/**/*.py", "paddle_tpu/*.py",
                     "tools/*.py", "examples/*.py", "tests/*.py")

    def __init__(self, flags_path=None, readme_path=None, scope=None):
        self.flags_path = flags_path or self.DEFAULT_FLAGS_PATH
        self.readme_path = readme_path or self.DEFAULT_README_PATH
        self.scope = tuple(scope or self.DEFAULT_SCOPE)

    def _defined(self, ctx):
        """{flag name: lineno} of define_flag literals in flags.py."""
        sf = ctx.file(self.flags_path)
        if sf is None or sf.tree is None:
            return None, None
        defined = {}
        for call in walk_calls(sf.tree):
            if call_name(call) in ("define_flag", "flags.define_flag"):
                name = str_arg(call)
                if name is not None:
                    defined[name] = call.lineno
        return defined, sf

    def _documented(self, ctx):
        sf = ctx.file(self.readme_path)
        if sf is None:
            return {}, None
        documented = {}
        for lineno, cell in _table_rows(sf.lines):
            for tok in _FLAG_TOKEN.findall(cell):
                documented.setdefault(tok, lineno)
        return documented, sf

    def check(self, ctx):
        defined, flags_sf = self._defined(ctx)
        if defined is None:
            yield Finding(self.name, self.flags_path, 1,
                          f"flag registry {self.flags_path} missing or "
                          "unparseable — the rule's anchor rotted")
            return
        documented, readme_sf = self._documented(ctx)
        if readme_sf is None:
            yield Finding(self.name, self.readme_path, 1,
                          f"{self.readme_path} not found — flag table "
                          "unavailable")
            return

        for flag, lineno in sorted(defined.items()):
            if flag not in documented:
                yield Finding(
                    self.name, flags_sf.relpath, lineno,
                    f"flag {flag!r} is defined but missing from the "
                    f"{self.readme_path} flag table")
        for flag, lineno in sorted(documented.items()):
            if flag not in defined:
                yield Finding(
                    self.name, readme_sf.relpath, lineno,
                    f"flag table documents {flag!r} but core/flags.py "
                    "defines no such flag (non-flag knobs belong "
                    "outside the `Flag` column's bare-name namespace)")

        for sf in ctx.glob(*self.scope):
            if sf.tree is None or sf.relpath == self.flags_path:
                continue
            for call in walk_calls(sf.tree):
                cn = call_name(call)
                if cn is not None and cn.split(".")[-1] == "get_flag":
                    name = str_arg(call)
                    if name is not None and name not in defined:
                        yield Finding(
                            self.name, sf.relpath, call.lineno,
                            f"get_flag({name!r}) reads an undefined "
                            "flag")
                elif cn is not None and cn.split(".")[-1] == "set_flags":
                    if call.args and isinstance(call.args[0], ast.Dict):
                        for k in call.args[0].keys:
                            if (isinstance(k, ast.Constant)
                                    and isinstance(k.value, str)
                                    and k.value not in defined):
                                yield Finding(
                                    self.name, sf.relpath, k.lineno,
                                    f"set_flags key {k.value!r} is not "
                                    "a defined flag")
