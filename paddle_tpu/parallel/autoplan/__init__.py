"""autoplan — cost-model-driven auto-parallelism.

Model + topology in, dp x tp x pp mesh + shardings + collective
strategy out (arxiv 2110.10548 style: enumerate legal placements over
the hierarchical topology, score with an analytic compute/memory/
collective cost model, pick the argmin):

    from paddle_tpu.parallel import autoplan

    spec = autoplan.ModelSpec.from_config(GPTConfig.small(),
                                          batch=32, seq=1024)
    mp = autoplan.plan(spec, topology="v5e-8")
    print(mp.describe())            # ranked candidate table + reasons
    mesh = mp.build_mesh()
    params = mp.place(params)       # LM layout via DistributionPlanner
    loss = model.loss(ids, mesh_plan=mp)

Entry points elsewhere: ``fleet.auto_plan(...)`` +
``distributed_optimizer(strategy="auto")``, ``Trainer(mesh_plan=...)``,
``tools/compile_smoke.py --autoplan``, and the ``tools/autoplan.py`` CLI.
"""

from paddle_tpu.parallel.autoplan.costmodel import (  # noqa: F401
    ModelSpec, calibration_report, chip_memory, collective_bytes,
    train_flops)
from paddle_tpu.parallel.autoplan.layouts import lm_layout  # noqa: F401
from paddle_tpu.parallel.autoplan.search import (  # noqa: F401
    Candidate, MeshPlan, NoFeasiblePlanError, factorizations, plan)
from paddle_tpu.parallel.autoplan.topology import (  # noqa: F401
    Topology, detect, get_topology)

__all__ = [
    "Candidate", "MeshPlan", "ModelSpec", "NoFeasiblePlanError",
    "Topology", "calibration_report", "chip_memory", "collective_bytes",
    "detect", "factorizations", "get_topology", "lm_layout", "plan",
    "train_flops",
]
