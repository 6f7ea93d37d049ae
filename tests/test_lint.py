"""graft-lint tier-1: the tree is clean AND every detector detects.

Two halves, mirroring the PT_FUSED_XENT=0 convention the compile smoke
established: (1) the real tree produces zero findings — drift, hot-path
syncs, tracer leaks, and committed logs are build breakers from here on;
(2) every AST rule and every contract class is run against a planted
violation under tests/fixtures/lint/ and must FIRE — a detector that
stops detecting fails here, not silently.
"""

import os

import pytest

from paddle_tpu.analysis import contracts, lint
from paddle_tpu.analysis.rules.catalog_drift import CatalogDrift
from paddle_tpu.analysis.rules.event_drift import EventDrift
from paddle_tpu.analysis.rules.fault_point_drift import FaultPointDrift
from paddle_tpu.analysis.rules.flag_drift import FlagDrift
from paddle_tpu.analysis.rules.hot_path_sync import HotPathSync
from paddle_tpu.analysis.rules.lock_order import LockOrder
from paddle_tpu.analysis.rules.no_committed_logs import NoCommittedLogs
from paddle_tpu.analysis.rules.raw_pallas_call import RawPallasCall
from paddle_tpu.analysis.rules.thread_unsafe_publish import (
    ThreadUnsafePublish)
from paddle_tpu.analysis.rules.tracer_leak import TracerLeak
from paddle_tpu.analysis.rules.unguarded_shared_state import (
    UnguardedSharedState)

pytestmark = pytest.mark.lint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIX = os.path.join(REPO, "tests", "fixtures", "lint")
_ALL = ("**/*.py", "*.py")   # fixture trees are tiny; scope everything


def _fixture_ctx(sub):
    return lint.LintContext(os.path.join(FIX, sub))


def _hlo(name):
    with open(os.path.join(FIX, "contracts", name)) as fh:
        return fh.read()


# --- half 1: the tree is clean ---------------------------------------

def test_tree_has_zero_findings():
    """python tools/graft_lint.py parity: the full registry over the
    whole repo, suppressions honored, no findings."""
    findings = lint.run_lint(lint.LintContext(REPO))
    assert findings == [], "\n" + "\n".join(f.format() for f in findings)


def test_every_tree_suppression_carries_a_reason():
    """The clean run above already fails on reasonless suppressions;
    this pins the inventory so a new suppression shows up in review."""
    ctx = lint.LintContext(REPO)
    suppressed = []
    for sf in ctx.files:
        if (sf.relpath.startswith("paddle_tpu/analysis/")
                or sf.relpath == "tools/graft_lint.py"):
            continue   # the framework documents the syntax in docstrings
        for i, line in enumerate(sf.lines, 1):
            sup = lint.parse_suppressions(line)
            if sup is not None:
                suppressed.append((sf.relpath, i, sup))
    # 2 telemetry trailing fetches + 2 guardian trailing fetches
    # + 2 serving-engine scheduler syncs: the trailing wait for the
    # round before the one just launched (an admission's first token
    # waits at the same site) and the speculative verify round
    assert len(suppressed) == 6, suppressed
    for relpath, lineno, (rules, reason) in suppressed:
        assert reason, f"{relpath}:{lineno} suppression without reason"
        assert rules == ("hot-path-sync",), (relpath, lineno, rules)


# --- half 2: every rule fires on its planted fixture -----------------

def test_hot_path_sync_fixture_fires():
    rule = HotPathSync(
        modules=("paddle_tpu/serving/engine.py",),
        roots=(("paddle_tpu/serving/engine.py", "ServingEngine.step"),))
    fs = list(rule.check(_fixture_ctx("hot_path_sync")))
    lines = sorted(f.line for f in fs)
    assert len(fs) == 4, [f.format() for f in fs]
    # np.asarray-on-device, block_until_ready, device_get (via the
    # step -> _count call-graph edge), .item()
    assert lines == [14, 15, 20, 21], [f.format() for f in fs]
    # the host-side np.asarray([1, 2, 3]) on line 16 stays silent
    assert 16 not in lines


def test_tracer_leak_fixture_fires():
    rule = TracerLeak(scope=_ALL)
    fs = list(rule.check(_fixture_ctx("tracer_leak")))
    lines = sorted(f.line for f in fs)
    # `if x`, `while x` (via lax.scan), IfExp, bool()
    assert lines == [12, 18, 34, 35], [f.format() for f in fs]


def test_flag_drift_fixture_fires_both_directions():
    rule = FlagDrift(scope=_ALL)
    fs = list(rule.check(_fixture_ctx("flag_drift")))
    msgs = [f.message for f in fs]
    assert len(fs) == 4, [f.format() for f in fs]
    assert any("'undocumented'" in m and "missing from" in m for m in msgs)
    assert any("'ghost'" in m and "no such flag" in m for m in msgs)
    assert any("get_flag('missing_flag')" in m for m in msgs)
    assert any("'also_missing'" in m for m in msgs)


def test_catalog_drift_fixture_fires():
    rule = CatalogDrift(scope=_ALL, min_sites=1)
    fs = list(rule.check(_fixture_ctx("catalog_drift")))
    msgs = [f.message for f in fs]
    assert len(fs) == 2, [f.format() for f in fs]
    assert any("'rogue.metric'" in m for m in msgs)
    assert any("cataloged as gauge" in m for m in msgs)


def test_fault_point_drift_fixture_fires_both_directions():
    rule = FaultPointDrift(scope=_ALL, min_sites=1)
    fs = list(rule.check(_fixture_ctx("fault_point_drift")))
    msgs = [f.message for f in fs]
    assert len(fs) == 2, [f.format() for f in fs]
    assert any("'rogue.point'" in m for m in msgs)
    assert any("'unused.point'" in m for m in msgs)


def test_event_drift_fixture_fires_both_directions():
    rule = EventDrift(scope=_ALL, min_sites=1)
    fs = list(rule.check(_fixture_ctx("event_drift")))
    msgs = [f.message for f in fs]
    assert len(fs) == 2, [f.format() for f in fs]
    assert any("'rogue.event'" in m and "not registered" in m
               for m in msgs)
    assert any("'unused.event'" in m and "never happens" in m
               for m in msgs)


def test_raw_pallas_call_fixture_fires():
    rule = RawPallasCall(scope=_ALL, min_sites=1)
    fs = list(rule.check(_fixture_ctx("raw_pallas_call")))
    assert len(fs) == 1, [f.format() for f in fs]
    assert fs[0].path == "user.py" and "kernel_call" in fs[0].message
    # the allowed wrapper module's own site stays silent, and counts
    # toward the rot canary (min_sites=1 satisfied by core.py alone)


def test_raw_pallas_call_rot_canary():
    rule = RawPallasCall(scope=_ALL, min_sites=10)
    fs = list(rule.check(_fixture_ctx("raw_pallas_call")))
    assert any("detection rotted" in f.message for f in fs)


def test_no_committed_logs_fixture_fires():
    rule = NoCommittedLogs(use_git=False)   # fixture tree is not a repo
    fs = list(rule.check(_fixture_ctx("no_committed_logs")))
    assert [f.path for f in fs] == ["tools/stale.log"]


def test_unguarded_shared_state_fixture_fires():
    rule = UnguardedSharedState(
        modules=("svc.py",), roots=(("svc.py", "Service.submit"),))
    fs = list(rule.check(_fixture_ctx("unguarded_shared_state")))
    lines = sorted(f.line for f in fs)
    # 27/28: Thread(target=self._loop) entry, inline + GUARDED_BY forms;
    # 34: append after the `with` closed, via the client-facing root;
    # 54: docstring form, reached through the action= callback kwarg
    assert lines == [27, 28, 34, 54], [f.format() for f in fs]
    msgs = {f.line: f.message for f in fs}
    assert "Service._lock" in msgs[27] and "Thread(target" in msgs[27]
    assert "self.table" in msgs[28]
    assert "client-facing Service.submit" in msgs[34]
    assert "DocGuarded._mu" in msgs[54] and "action" in msgs[54]
    # _drain's clear() is only reached with the lock held: silent
    assert 37 not in lines


def test_unguarded_shared_state_root_rot_canary():
    rule = UnguardedSharedState(
        modules=("svc.py",), roots=(("svc.py", "Service.vanished"),))
    fs = list(rule.check(_fixture_ctx("unguarded_shared_state")))
    assert any("rotted" in f.message for f in fs), \
        [f.format() for f in fs]


def test_lock_order_fixture_fires():
    rule = LockOrder(modules=("ab.py",))
    fs = list(rule.check(_fixture_ctx("lock_order")))
    assert len(fs) == 1, [f.format() for f in fs]
    assert fs[0].line == 18
    assert "A._lock" in fs[0].message and "B._lock" in fs[0].message


def test_thread_unsafe_publish_fixture_fires():
    rule = ThreadUnsafePublish(modules=("pub.py",))
    fs = list(rule.check(_fixture_ctx("thread_unsafe_publish")))
    assert len(fs) == 1, [f.format() for f in fs]
    assert fs[0].line == 20
    assert "self.items" in fs[0].message
    assert "Board.publish" in fs[0].message
    # list(self.safe) snapshots and self.locked shares the lock: silent


def test_stale_suppression_fixture_fires():
    """Quiet.read holds the lock, so its disable comment swallows
    nothing -> stale; Quiet.peek really races, so its suppression stays
    live (and silent)."""
    ctx = _fixture_ctx("stale_suppression")
    rule = UnguardedSharedState(
        modules=("mod.py",),
        roots=(("mod.py", "Quiet.read"), ("mod.py", "Quiet.peek")))
    fs = lint.run_lint(ctx, rules=[rule])
    assert [(f.rule, f.line) for f in fs] == [
        ("stale-suppression", 18)], [f.format() for f in fs]
    assert "unguarded-shared-state" in fs[0].message


def test_stale_suppression_only_judges_rules_that_ran():
    """A --rules subset pass must not flag suppressions of rules it
    did not run."""
    ctx = _fixture_ctx("stale_suppression")
    fs = lint.run_lint(ctx, rules=[TracerLeak(scope=_ALL)])
    assert fs == [], [f.format() for f in fs]


def test_cli_fail_on_gates_warn_level_findings(tmp_path, capsys):
    """stale-suppression is warn-level: the default --fail-on warn run
    fails on it, --fail-on error reports it but exits clean."""
    import json

    import tools.graft_lint as gl
    # concatenation keeps THIS file's scan from seeing a suppression
    (tmp_path / "m.py").write_text(
        "x = 1  # graft-lint: " + "disable=tracer-leak (obsolete)\n")
    argv = ["--root", str(tmp_path), "--rules", "tracer-leak",
            "--format", "json"]
    assert gl.main(argv) == 1
    out = json.loads(capsys.readouterr().out)
    assert [f["rule"] for f in out["findings"]] == ["stale-suppression"]
    assert out["findings"][0]["severity"] == "warn"
    assert not out["ok"]
    assert gl.main(argv + ["--fail-on", "error"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert [f["rule"] for f in out["findings"]] == ["stale-suppression"]
    assert out["ok"]


def test_parse_contract_names_handles_commas_in_row_names():
    """Mesh specs put commas inside row names (train.gpt@dp2,tp2) — the
    --contracts parser must re-merge split tokens, not shred them."""
    import tools.graft_lint as gl
    known = {"train.gpt@dp2,tp2", "serve.decode", "mlp.fused"}
    assert gl._parse_contract_names(
        "train.gpt@dp2,tp2,serve.decode", known) == [
            "train.gpt@dp2,tp2", "serve.decode"]
    assert gl._parse_contract_names("serve.decode", known) == [
        "serve.decode"]
    assert gl._parse_contract_names("all", known) == sorted(known)
    with pytest.raises(SystemExit, match="unknown contract"):
        gl._parse_contract_names("train.gpt@dp2,nope", known)


def test_changed_only_diffs_against_merge_base_with_main():
    """_changed_paths must key on the merge-base with main (not HEAD):
    on a branch, already-committed work still lints."""
    import tools.graft_lint as gl
    base = gl._git("merge-base", "HEAD", "main").strip()
    head = gl._git("rev-parse", "HEAD").strip()
    assert base and head
    paths = gl._changed_paths()
    expected = {
        p for p in gl._git("diff", "--name-only", base).splitlines()
        if p.strip()}
    assert expected <= paths
    # untracked python files ride along too (set comparison above
    # already allows them; just pin the filter to .py)
    for p in paths - expected:
        assert p.endswith(".py"), p


def test_suppression_machinery():
    """Reasoned suppression swallows; reasonless does not and is itself
    a finding; unknown rule names are findings."""
    ctx = _fixture_ctx("suppressions")
    rule = FaultPointDrift(scope=_ALL, min_sites=1)
    fs = lint.run_lint(ctx, rules=[rule])
    by_rule = {}
    for f in fs:
        by_rule.setdefault(f.rule, []).append(f)
    fp_lines = sorted(f.line for f in by_rule["fault-point-drift"])
    assert fp_lines == [7, 8], [f.format() for f in fs]   # 6 suppressed
    bad = sorted(f.line for f in by_rule["bad-suppression"])
    assert bad == [7, 8], [f.format() for f in fs]
    # line 7: missing reason; line 8: unknown rule
    msgs = {f.line: f.message for f in by_rule["bad-suppression"]}
    assert "without a reason" in msgs[7]
    assert "imaginary-rule" in msgs[8]


# --- every contract class fires on planted HLO/jaxpr -----------------

def test_no_temporary_contract_fires_and_clears():
    no_tmp = contracts.NoTemporary({512, 256}, 512)
    assert no_tmp.temporaries(_hlo("vocab_temporary.hlo")) == [(1024, 512)]
    assert no_tmp.temporaries(_hlo("clean_sharded.hlo")) == []
    assert no_tmp.check(contracts.ContractContext(
        hlo_text=_hlo("vocab_temporary.hlo")))
    # the serve-shape variant on a planted dense decode score
    serve_tmp = contracts.NoTemporary({48}, 8)
    assert serve_tmp.temporaries(_hlo("dense_score.hlo")) == [
        (2, 4, 48), (2, 4, 48, 16)]


def test_no_op_matching_contract_fires_and_clears():
    ag = contracts.NoOpMatching(
        "all-gather",
        shape_test=lambda shp: 512 in shp and len(shp) >= 2)
    assert ag.matches(_hlo("weight_all_gather.hlo"))
    # the benign small all-gather in the clean module stays silent
    assert ag.matches(_hlo("clean_sharded.hlo")) == []


def test_traced_once_contract():
    c = contracts.TracedOnce(("serve.decode",))
    ok = contracts.ContractContext(trace_counts={"serve.decode": 1})
    retraced = contracts.ContractContext(trace_counts={"serve.decode": 3})
    missing = contracts.ContractContext(trace_counts={})
    assert c.check(ok) == []
    assert "traced 3x" in c.check(retraced)[0]
    assert "no trace count" in c.check(missing)[0]


def test_donation_respected_contract():
    c = contracts.DonationRespected(min_aliases=1)
    aliased = contracts.ContractContext(hlo_text=_hlo("clean_sharded.hlo"))
    copied = contracts.ContractContext(hlo_text=_hlo("undonated.hlo"))
    assert c.check(aliased) == []
    assert "donated buffer is being copied" in c.check(copied)[0]


def test_no_host_callback_contract():
    c = contracts.NoHostCallback()
    hlo_hits = c.check(contracts.ContractContext(
        hlo_text=_hlo("host_callback.hlo")))
    assert any("infeed" in m for m in hlo_hits)
    assert any("callback" in m for m in hlo_hits)
    jaxpr_hits = c.check(contracts.ContractContext(
        jaxpr_text=_hlo("pure_callback.jaxpr")))
    assert any("pure_callback" in m for m in jaxpr_hits)
    assert any("debug_callback" in m for m in jaxpr_hits)
    assert c.check(contracts.ContractContext(
        hlo_text=_hlo("clean_sharded.hlo"))) == []


def test_max_dtype_width_contract():
    c = contracts.MaxDtypeWidth(32)
    hits = c.check(contracts.ContractContext(
        hlo_text=_hlo("f64_promotion.hlo")))
    assert hits and "f64" in hits[0]
    assert c.check(contracts.ContractContext(
        hlo_text=_hlo("clean_sharded.hlo"))) == []


def test_max_hlo_budget_contract_fires_holds_and_is_vacuous():
    b = contracts.MaxHloFlops(100.0, 1.5, source="unit")
    under = contracts.ContractContext(cost={"flops": 120.0})
    over = contracts.ContractContext(cost={"flops": 200.0})
    assert b.check(under) == []
    assert "exceeds budget" in b.check(over)[0]
    assert "unit" in b.check(over)[0]
    # tolerance=0 positive control: any real compile trips
    assert b.with_tolerance(0).check(under)
    # no cost dict -> vacuous; cost without the key -> loud
    assert b.check(contracts.ContractContext(hlo_text="x")) == []
    assert "no 'flops' metric" in b.check(
        contracts.ContractContext(cost={"bytes accessed": 1.0}))[0]
    by = contracts.MaxHloBytes(1000.0, 2.0)
    assert by.check(contracts.ContractContext(
        cost={"bytes accessed": 1999.0})) == []
    assert by.check(contracts.ContractContext(
        cost={"bytes accessed": 2001.0}))


def test_budget_rows_are_priced_by_the_cost_model():
    """The train.gpt and serve.decode rows carry budgets whose predicted
    figures come out of costmodel.predict()/predict_decode() — never a
    hand-written constant (the source string records the pricing call,
    and re-deriving the prediction here must reproduce it)."""
    for key, fn in (("train.gpt@dp2,tp2", "costmodel.predict"),
                    ("serve.decode", "costmodel.predict_decode")):
        budgets = [b for b in contracts.CONTRACTS[key]
                   if isinstance(b, contracts.MaxHloCost)]
        assert {type(b) for b in budgets} == {
            contracts.MaxHloFlops, contracts.MaxHloBytes}, key
        for b in budgets:
            assert b.predicted > 0 and b.tolerance > 0, (key, b.name)
            assert fn in b.source, (key, b.source)
    cm = contracts._load_autoplan("costmodel")
    topo = contracts._load_autoplan("topology").get_topology("cpu4")
    pred = cm.predict(contracts._train_spec("gpt"), topo, dp=2, tp=2,
                      pp=1, rate=topo.peak_flops * cm.MFU_ASSUMED)
    flops_budget = next(
        b for b in contracts.CONTRACTS["train.gpt@dp2,tp2"]
        if isinstance(b, contracts.MaxHloFlops))
    assert flops_budget.predicted == pred["flops_per_chip"]


def test_sharded_case_gpt_matches_tiny_config():
    """Drift guard: the budget pricing reuses the gpt ShardedCase depth
    fields as the cost-model spec, so they must mirror GPTConfig.tiny
    (what tools/compile_smoke.py train_program compiles)."""
    from paddle_tpu.models.gpt import GPTConfig
    cfg = GPTConfig.tiny()
    case = contracts.SHARDED_TRAIN_CASES["gpt"]
    assert (case.vocab, case.hidden, case.layers, case.heads,
            case.intermediate, case.max_position) == (
        cfg.vocab_size, cfg.hidden_size, cfg.num_layers, cfg.num_heads,
        cfg.intermediate_size, cfg.max_position)


def test_hlo_snapshot_gate_blesses_checks_and_trips(tmp_path):
    snap = contracts.HloSnapshot("unit.case", snapshot_dir=str(tmp_path))
    text = _hlo("clean_sharded.hlo")
    # unblessed -> loud
    assert "no blessed snapshot" in snap.check(
        contracts.ContractContext(hlo_text=text))[0]
    rec = snap.bless(text)
    assert rec["hash"] and rec["ops"]
    # same module -> clean; text-free context -> vacuous
    assert snap.check(contracts.ContractContext(hlo_text=text)) == []
    assert snap.check(contracts.ContractContext()) == []
    # a structural change (one extra fusion instruction) -> drift
    drifted = text + "\n  %x.9 = f32[4]{0} sort(f32[4]{0} %p9)\n"
    msg = snap.check(contracts.ContractContext(hlo_text=drifted))
    assert msg and "drifted" in msg[0] and "sort" in msg[0], msg


def test_registered_snapshots_are_blessed_on_disk():
    """Every CONTRACT_SNAPSHOTS row has a committed blessed record —
    compile_smoke judges against these; a missing file would turn the
    gate into a permanent failure."""
    assert set(contracts.CONTRACT_SNAPSHOTS) == {
        "train.gpt@dp2,tp2", "serve.decode", "serve.decode@int8",
        "serve.verify"}
    for key, snap in contracts.CONTRACT_SNAPSHOTS.items():
        rec = snap.load()
        assert rec is not None, f"{key}: no blessed snapshot at {snap.path}"
        assert rec["key"] == key
        assert rec["hash"] == contracts._ops_hash(rec["ops"])


def test_hlo_op_histogram_counts_instructions():
    ops = contracts.hlo_op_histogram(_hlo("clean_sharded.hlo"))
    assert ops, "histogram empty on a real module"
    # every module has parameters and a root computation
    assert ops.get("parameter"), ops


def test_contract_table_rows_fire_on_planted_modules():
    """Drive the planted HLO through the same CONTRACTS rows the compile
    smoke evaluates — the full row trips, not just the lone class."""
    row = contracts.CONTRACTS["train.gpt@dp2,tp2"]
    vs = contracts.evaluate(row, contracts.ContractContext(
        hlo_text=_hlo("vocab_temporary.hlo")))
    assert any("no-temporary" in v.contract for v in vs), vs
    serve_row = contracts.CONTRACTS["serve.decode"]
    vs = contracts.evaluate(serve_row, contracts.ContractContext(
        hlo_text=_hlo("dense_score.hlo"),
        trace_counts={"serve.decode": 1}))
    assert any("no-temporary" in v.contract for v in vs), vs
    clean = contracts.evaluate(row, contracts.ContractContext(
        hlo_text=_hlo("clean_sharded.hlo")))
    assert clean == [], [v.format() for v in clean]
