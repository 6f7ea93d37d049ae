"""Autoplan subsystem tests: the shared LM layout table, the
cost-model's calibration against XLA's own cost_analysis, the
factorization search on synthetic topologies (every prune carries a
recorded reason), and the consumption surface — fleet strategy="auto",
Trainer(mesh_plan=...), MeshPlan placement on the virtual 8-chip mesh.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.core.enforce import EnforceError
from paddle_tpu.parallel.autoplan import (MeshPlan, ModelSpec,
                                          NoFeasiblePlanError, Topology,
                                          get_topology, layouts, plan)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tiny_spec(**kw):
    base = dict(name="tiny", vocab=1024, hidden=64, layers=2, heads=4,
                intermediate=128, seq=32, batch=64)
    base.update(kw)
    return ModelSpec(**base)


class TestLayouts:
    """One source of truth: api.tp_lm_specs and the planner's lm_rules
    both resolve through layouts.lm_layout."""

    def test_known_rows(self):
        t, r = layouts.lm_layout(("tok_emb", "weight"), (50304, 64))
        assert t == ("tp", None) and "vocab" in r
        t, _ = layouts.lm_layout(("out_proj", "weight"), (64, 50304))
        assert t == (None, "tp")
        t, _ = layouts.lm_layout(("mlm_bias",), (50304,))
        assert t == ("tp",)
        # small 2-D weights stay replicated
        t, _ = layouts.lm_layout(("ln", "weight"), (8, 8))
        assert t == (None, None)

    def test_non_divisible_downgrades_with_reason(self):
        t, r = layouts.lm_layout(("out_proj", "weight"), (64, 50305),
                                 tp_size=4)
        assert t == (None, None)
        assert "SKIPPED" in r and "50305" in r

    def test_tp1_strips_axes(self):
        """tp_size=1 means the mesh has NO tp axis: every LM target must
        come back fully replicated or NamedSharding will reject the
        spec (the bench --mesh auto pure-dp regression)."""
        for names, shape in [(("tok_emb", "weight"), (50304, 64)),
                             (("out_proj", "weight"), (64, 50304)),
                             (("mlm_bias",), (50304,))]:
            t, r = layouts.lm_layout(names, shape, tp_size=1)
            assert all(a is None for a in t), (names, t, r)

    def test_tp_lm_specs_parity(self):
        """The legacy helper delegates to the same table."""
        from jax.sharding import PartitionSpec as P
        from paddle_tpu.parallel.api import tp_lm_specs
        specs = tp_lm_specs({"tok_emb": {"weight": np.zeros((4096, 64))},
                             "out_proj": {"weight": np.zeros((64, 4096))},
                             "ln": {"weight": np.zeros((64,))}})
        assert specs["tok_emb"]["weight"] == P("tp", None)
        assert specs["out_proj"]["weight"] == P(None, "tp")
        assert specs["ln"]["weight"] == P()


class TestPlannerFoldIn:
    def test_lm_rules_emit_shared_layout(self):
        from paddle_tpu.parallel.mesh import make_mesh
        from paddle_tpu.parallel.planner import DistributionPlanner
        mesh = make_mesh({"dp": 4, "tp": 2})
        params = {"tok_emb": {"weight": np.zeros((4096, 64))},
                  "out_proj": {"weight": np.zeros((64, 4096))}}
        entries = DistributionPlanner(mesh, lm_rules=True).plan_params(
            params)
        assert entries["tok_emb/weight"].spec == ("tp", None)
        assert entries["out_proj/weight"].spec == (None, "tp")

    def test_tp_skip_records_reason_never_raises(self):
        """Satellite: the generic tp rule must record the skip, not
        raise, when no dim divides."""
        from paddle_tpu.parallel.mesh import make_mesh
        from paddle_tpu.parallel.planner import DistributionPlanner
        mesh = make_mesh({"dp": 4, "tp": 2})
        params = {"odd": {"w": np.zeros((3, 5))}}
        entries = DistributionPlanner(
            mesh, tp_patterns=("odd",)).plan_params(params)
        e = entries["odd/w"]
        assert e.spec == (None, None)
        assert "tp SKIPPED" in e.reason and "(3, 5)" in e.reason


class TestSearch:
    def test_huge_vocab_forces_tp(self):
        """Vocab-dominated memory over tiny HBM: pure dp must be pruned
        (with the memory reason on record) and the winner carries tp."""
        tight = Topology(name="tight4", num_chips=4,
                         hbm_bytes=3 * 2 ** 30, peak_flops=1e12,
                         intra_bw=1e11, inter_bw=1e10)
        big = ModelSpec(name="big-vocab", vocab=512 * 1024, hidden=1024,
                        layers=4, heads=16, intermediate=4096, seq=128,
                        batch=8)
        p = plan(big, topology=tight, allow_pp=False)
        assert p.tp > 1, p.axes
        dp_only = next(c for c in p.candidates
                       if c.dp == 4 and c.tp == 1)
        assert not dp_only.feasible
        assert any("HBM" in r for r in dp_only.reasons), dp_only.reasons

    def test_tiny_model_on_big_slice_pure_dp(self):
        roomy = Topology(name="roomy8", num_chips=8,
                         hbm_bytes=32 * 2 ** 30, peak_flops=1e14,
                         intra_bw=2e11, inter_bw=2.5e10)
        p = plan(_tiny_spec(), topology=roomy)
        assert p.axes == {"dp": 8}, p.axes

    def test_pp_only_when_layers_cover_stages(self):
        roomy = Topology(name="roomy8", num_chips=8,
                         hbm_bytes=32 * 2 ** 30, peak_flops=1e14,
                         intra_bw=2e11, inter_bw=2.5e10)
        p = plan(_tiny_spec(layers=2), topology=roomy)
        for c in p.candidates:
            if c.pp > 2:
                assert not c.feasible
                assert any("layers" in r for r in c.reasons), c.reasons

    def test_no_feasible_raises_with_every_reason(self):
        starved = Topology(name="starved2", num_chips=2, hbm_bytes=2 ** 20,
                           peak_flops=1e12, intra_bw=1e11, inter_bw=1e10)
        with pytest.raises(NoFeasiblePlanError) as ei:
            plan(_tiny_spec(), topology=starved, allow_pp=False)
        msg = str(ei.value)
        assert "dp2" in msg and "tp2" in msg and "GiB" in msg

    def test_json_roundtrip(self):
        p = plan(_tiny_spec(), topology=get_topology("cpu4"))
        rt = MeshPlan.from_json(json.loads(p.dumps()))
        assert rt.axes == p.axes
        assert rt.schedule == p.schedule
        assert len(rt.candidates) == len(p.candidates)
        assert rt.topology.hbm_bytes == p.topology.hbm_bytes
        assert rt.summary() == p.summary()

    def test_topology_name_parsing(self):
        assert get_topology("cpu4").num_chips == 4
        t = get_topology("v5e-8")
        assert t.num_chips == 8 and t.hbm_bytes == 16 * 2 ** 30
        t2 = get_topology("2xv5e-16")
        assert t2.num_chips == 32 and t2.num_slices == 2
        assert t2.chips_per_slice == 16
        # dp across slices prices at DCN, inside a slice at ICI
        assert t2.axis_bandwidth(crosses_slices=True) < \
            t2.axis_bandwidth(crosses_slices=False)


class TestCalibration:
    """The analytic flop model vs jit(...).lower().compile()
    .cost_analysis() on CPU — the band is deliberately loose (XLA
    counts fusion-dependent flops) but one-sided errors beyond ~40%
    mean the model diverged from the lowering."""

    def _check(self, model):
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "autoplan.py"),
             "--model", model, "--calibrate", "--tiny",
             "--batch", "2", "--seq", "16"],
            stdout=subprocess.PIPE, text=True, timeout=420,
            env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO)
        row = json.loads(out.stdout.strip().splitlines()[-1])
        assert row["measured_flops"] > 0, row
        assert 0.6 < row["ratio"] < 1.6, row
        # the constants block labels which source prices compute (the
        # autotune-cache measured rate vs the analytic MFU assumption)
        assert row["constants"]["rate_source"] in ("measured", "analytic")
        assert row["constants"]["rate_flops_s"] > 0, row
        return row

    def test_gpt_flops_within_band(self):
        self._check("gpt")

    def test_bert_flops_within_band(self):
        self._check("bert")


class TestConsumption:
    def test_fleet_strategy_auto(self):
        from paddle_tpu.parallel import fleet
        try:
            p = fleet.auto_plan(spec=_tiny_spec(), topology="cpu8",
                                allow_pp=False)
            assert fleet.mesh_plan is p
            mesh = fleet.build_mesh(strategy="auto")
            n = 1
            for v in mesh.shape.values():
                n *= v
            assert n == 8
            opt = fleet.distributed_optimizer(pt.optimizer.SGD(0.1),
                                              strategy="auto")
            assert opt is not None
        finally:
            fleet._auto_plan = None
            fleet._strategy = None

    def test_strategy_auto_without_plan_raises(self):
        from paddle_tpu.parallel import fleet
        fleet._auto_plan = None
        with pytest.raises(EnforceError, match="auto_plan"):
            fleet.build_mesh(strategy="auto")

    def test_meshplan_place_and_loss_kwargs(self):
        forced = MeshPlan(model="gpt-tiny", topology=get_topology("cpu8"),
                          axes={"dp": 4, "tp": 2}, schedule="1f1b",
                          microbatches=1, predicted={}, reason="forced",
                          candidates=[])
        params = {"tok_emb": {"weight": np.zeros((4096, 64), np.float32)},
                  "out_proj": {"weight": np.zeros((64, 4096), np.float32)},
                  "ln": {"weight": np.zeros((64,), np.float32)}}
        placed = forced.place(params)
        emb = placed["tok_emb"]["weight"]
        assert emb.sharding.spec == jax.sharding.PartitionSpec("tp", None)
        assert forced.entries["tok_emb/weight"].spec == ("tp", None)
        kw = forced.loss_kwargs()
        assert kw["vocab_axis"] == "tp" and kw["batch_axis"] == "dp"
        # explicit values win over the plan's
        assert forced.resolve_loss_axes("v", "b", None)[:2] == ("v", "b")

    def test_meshplan_pure_dp_replicates(self):
        forced = MeshPlan(model="gpt-tiny", topology=get_topology("cpu8"),
                          axes={"dp": 8}, schedule="1f1b", microbatches=1,
                          predicted={}, reason="forced", candidates=[])
        placed = forced.place(
            {"tok_emb": {"weight": np.zeros((4096, 64), np.float32)}})
        assert all(a is None for a in
                   forced.entries["tok_emb/weight"].spec)
        kw = forced.loss_kwargs()
        assert kw["vocab_axis"] is None and kw["batch_axis"] == "dp"

    def test_trainer_consumes_mesh_plan(self):
        """train_from_dataset under a pure-dp MeshPlan: batches stage
        dp-sharded onto the planned mesh and the loop still converges."""
        from paddle_tpu.static import TrainerConfig, train_from_dataset
        rng = np.random.RandomState(0)
        d = 8
        w_true = rng.rand(d, 1).astype(np.float32)
        xs = rng.rand(256, d).astype(np.float32)
        ys = xs @ w_true
        ds = pt.data.InMemoryDataset(
            [(xs[i], ys[i]) for i in range(256)])
        mp = MeshPlan(model="linreg", topology=get_topology("cpu8"),
                      axes={"dp": 8}, schedule="1f1b", microbatches=1,
                      predicted={}, reason="forced", candidates=[])
        opt = pt.optimizer.SGD(0.2)
        params = {"w": jnp.zeros((d, 1))}
        state = {"params": params, "opt": opt.init(params)}

        @jax.jit
        def step(st, x, y):
            def loss_fn(p):
                return jnp.mean(jnp.square(x @ p["w"] - y))
            loss, grads = jax.value_and_grad(loss_fn)(st["params"])
            p, o = opt.apply_gradients(st["params"], grads, st["opt"])
            return loss, {"params": p, "opt": o}

        for _ in range(3):
            state, stats = train_from_dataset(
                step, state, ds, config=TrainerConfig(mesh_plan=mp),
                batch_size=32)
        assert stats["final_loss"] < 0.05


@pytest.mark.perf
def test_autoplan_mesh_hlo_contract():
    """Acceptance gate: the planner-resolved mesh (mesh="auto" on the
    cpu4 topology) compiles AND its per-device HLO passes the
    train.gpt@auto CONTRACTS row — same NoTemporary / no-vocab-all-gather
    judgments as the hand-picked dp2,tp2 row."""
    import tools.compile_smoke as cs
    out = cs.autoplan_check(model="gpt", topology="cpu4")
    assert out["clean"], out["violations"]
    assert out["plan"]["topology"] == "cpu4"
    assert out["mesh"] == out["plan"]["axes"]
    n = 1
    for v in out["plan"]["axes"].values():
        n *= v
    assert n == 4, out["plan"]


@pytest.mark.perf
def test_cli_selftest():
    """tools/autoplan.py --selftest is the tier-1 host-math gate."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "autoplan.py"),
         "--selftest"],
        stdout=subprocess.PIPE, text=True, timeout=180, cwd=REPO)
    assert out.returncode == 0
    assert json.loads(out.stdout.strip().splitlines()[-1])["ok"] is True
