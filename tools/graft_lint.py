#!/usr/bin/env python
"""graft-lint CLI: the repo's static-analysis front door.

Runs the AST rule layer (paddle_tpu/analysis/lint.py + rules/) over the
tree and exits non-zero on any finding. The heavy compile-contract layer
(paddle_tpu/analysis/contracts.py, evaluated against real compiled HLO)
is opt-in via --contracts because it compiles models.

Usage:
  python tools/graft_lint.py                    # whole tree, human output
  python tools/graft_lint.py --format json      # machine-readable
  python tools/graft_lint.py --changed-only     # pre-commit: only files
                                                #   this branch touches
                                                #   (merge-base w/ main)
  python tools/graft_lint.py --rules flag-drift,catalog-drift
  python tools/graft_lint.py --fail-on error    # warn-level findings
                                                #   report but exit 0
  python tools/graft_lint.py --list             # rules + contract table
  python tools/graft_lint.py --contracts serve.decode,train.gpt@dp2,tp2
  python tools/graft_lint.py --contracts all    # every CONTRACTS row
  python tools/graft_lint.py --contracts all --update-snapshots
                                                # re-bless HLO snapshots

tools/pre_commit.sh wraps the --changed-only form for .git/hooks.

The AST layer is stdlib-only and finishes in well under a second: the
repo package is entered through a namespace stub so paddle_tpu/__init__
(and with it jax) is never imported for a plain lint run.

Suppressions are per line, reason mandatory:
  x = np.asarray(d)  # graft-lint: disable=hot-path-sync (scheduler needs this)
"""

import argparse
import json
import os
import subprocess
import sys
import types

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _import_analysis():
    """paddle_tpu.analysis without paddle_tpu/__init__'s jax import: a
    namespace stub with the real package __path__ keeps submodule
    resolution intact while skipping the parent's side effects."""
    if "paddle_tpu" not in sys.modules:
        pkg = types.ModuleType("paddle_tpu")
        pkg.__path__ = [os.path.join(REPO, "paddle_tpu")]
        sys.modules["paddle_tpu"] = pkg
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from paddle_tpu.analysis import lint
    return lint


def _git(*args):
    proc = subprocess.run(
        ["git", "-C", REPO] + list(args),
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True)
    return proc.stdout if proc.returncode == 0 else ""


def _changed_paths(base_branch="main"):
    """Repo-relative paths this branch touches: diff against the
    merge-base with ``base_branch`` (NOT plain HEAD — work already
    committed on the branch still lints in a pre-push run), plus
    staged/unstaged edits and untracked .py files."""
    base = _git("merge-base", "HEAD", base_branch).strip() or "HEAD"
    paths = set()
    for extra in ([], ["--cached"]):
        out = _git("diff", "--name-only", base, *extra)
        paths.update(p for p in out.splitlines() if p.strip())
    out = _git("ls-files", "--others", "--exclude-standard")
    paths.update(p for p in out.splitlines()
                 if p.strip() and p.endswith(".py"))
    return paths


def _parse_contract_names(spec, known):
    """Split a --contracts value into row names. Row names themselves
    contain commas (mesh specs: ``train.gpt@dp2,tp2``), so a plain
    split would shred them — accumulate tokens until they match a
    known name instead."""
    if spec == "all":
        return sorted(known)
    names, cur = [], ""
    for tok in spec.split(","):
        tok = tok.strip()
        if not tok:
            continue
        cur = f"{cur},{tok}" if cur else tok
        if cur in known:
            names.append(cur)
            cur = ""
    if cur:
        raise SystemExit(f"unknown contract {cur!r}; "
                         f"known: {sorted(known)}")
    return names


def _run_contracts(spec, update_snapshots=False):
    """Evaluate CONTRACTS rows named by the --contracts value (compiles
    models — minutes, and imports jax). Returns findings-shaped dicts.
    ``update_snapshots`` re-blesses the HloSnapshot records instead of
    judging them."""
    import tools.compile_smoke as cs
    cs.want_cpu_devices(4)                # the train rows' dp2 x tp2 mesh
    sys.modules.pop("paddle_tpu", None)   # drop the stub: real jax now
    from paddle_tpu.analysis import contracts as c
    names = _parse_contract_names(spec, c.CONTRACTS)
    out = []
    for name in names:
        if name.startswith("train."):
            model, mesh = name[len("train."):].split("@")
            if mesh == "auto":
                res = cs.autoplan_check(model=model)
            else:
                res = cs.sharded_vocab_check(
                    model=model, mesh=mesh, positive_control=False,
                    update_snapshots=update_snapshots)
        else:
            res = cs.serve_smoke(update_snapshots=update_snapshots)
        if "snapshot_blessed" in res:
            print(f"blessed {name} snapshot: {res['snapshot_blessed']}",
                  file=sys.stderr)
        for v in res.get("violations", []):
            out.append({"rule": f"contract:{name}", "path": name,
                        "line": 0, "message": v, "severity": "error"})
        if not res.get("clean", False) and not res.get("violations"):
            out.append({"rule": f"contract:{name}", "path": name,
                        "line": 0, "severity": "error",
                        "message": f"contract row failed: {res}"})
    return out


def _emit_metrics(records, contract_records):
    """Count findings into the process-global registry so a CI harness
    that snapshots/exports metrics can trend which detectors fire.
    observability.metrics is stdlib-only, so a plain lint run still
    never imports jax."""
    from paddle_tpu.observability import metrics
    for r in records:
        metrics.counter("lint.findings").inc(rule=r["rule"])
    for r in contract_records:
        contract = r["rule"].split(":", 1)[-1]
        metrics.counter("contracts.violations").inc(contract=contract)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="repo static analysis: AST rules + compile contracts")
    ap.add_argument("--rules", default=None,
                    help="comma-separated rule subset (default: all)")
    ap.add_argument("--changed-only", action="store_true",
                    help="report only findings in files changed vs HEAD "
                         "(tree-wide rules still see the whole tree)")
    ap.add_argument("--format", choices=("human", "json"), default="human")
    ap.add_argument("--list", action="store_true",
                    help="list rules and contract rows, then exit")
    ap.add_argument("--contracts", default=None,
                    help="also evaluate these CONTRACTS rows ('all' or "
                         "comma-separated names) — compiles models, "
                         "needs jax")
    ap.add_argument("--update-snapshots", action="store_true",
                    help="with --contracts: re-bless the HloSnapshot "
                         "records under tests/fixtures/hlo_snapshots/ "
                         "instead of judging against them")
    ap.add_argument("--fail-on", choices=("warn", "error"),
                    default="warn",
                    help="minimum severity that fails the run: 'warn' "
                         "(default — any finding) or 'error' (advisory "
                         "warn-level findings are reported but exit 0)")
    ap.add_argument("--root", default=REPO, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    lint = _import_analysis()

    if args.list:
        print("rules:")
        for name, help_ in lint.rule_help().items():
            print(f"  {name:20s} {help_}")
        from paddle_tpu.analysis import contracts
        print("contracts (--contracts, compiles models):")
        for name, row in contracts.CONTRACTS.items():
            print(f"  {name:30s} {', '.join(c.name for c in row)}")
        return 0

    rules = None
    if args.rules:
        rules = lint.make_rules(
            [r.strip() for r in args.rules.split(",") if r.strip()])

    paths = _changed_paths() if args.changed_only else None
    ctx = lint.LintContext(args.root)
    findings = lint.run_lint(ctx, rules=rules, paths=paths)
    records = [f.as_dict() for f in findings]

    contract_records = []
    if args.contracts:
        contract_records = _run_contracts(
            args.contracts.strip(),
            update_snapshots=args.update_snapshots)
    _emit_metrics(records, contract_records)
    records += contract_records

    failing = [r for r in records
               if args.fail_on == "warn"
               or r.get("severity", "error") == "error"]
    if args.format == "json":
        print(json.dumps({"findings": records, "ok": not failing}))
    else:
        for r in records:
            print(f"{r['path']}:{r['line']}: [{r['rule']}] {r['message']}")
        n = len(records)
        scope = f"{len(paths)} changed file(s)" if paths is not None \
            else "tree"
        print(f"graft-lint: {n} finding(s) over {scope}"
              + ("" if n else " — clean")
              + ("" if len(failing) == n
                 else f" ({n - len(failing)} warn-level, not failing)"))
    return 1 if failing else 0


if __name__ == "__main__":
    raise SystemExit(main())
