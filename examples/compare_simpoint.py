"""SimPoint (BBV) vs two-phase RFV sampling, head to head.

Reproduces the paper's central comparison through the app-sharded sweep
engine: for each sampling plan, ONE ``run_sweep`` selects 20 regions per
app and projects CPI for all 7 microarchitecture configurations in a
single batched dispatch (sharded over an ``("app",)`` mesh when more
than one device is available). No host-side per-app or per-config loops
— the app argument may be one application or ``all`` for the full 10-app
matrix.

Designs are ``SamplingPlan`` objects (stratifier × selection policy ×
estimator): the third column swaps SimPoint's centroid policy for the
registry-provided ``RankedSetUnit`` order-statistic policy (per-stratum
median by phase-1 CPI rank, after *CPU Simulation with Ranked Set
Sampling and Repeated Subsampling*) — a plug-in that reaches the sweep
engine purely through the plan registry.

    PYTHONPATH=src python examples/compare_simpoint.py [app|all]
"""

import sys

from repro.core.sampling import (BBVClusters, Centroid, RankedSetUnit,
                                 RFVClusters, SamplingPlan)
from repro.experiments import ExperimentEngine, SweepSpec, run_sweep
from repro.runtime.compile_cache import enable_compile_cache
from repro.simcpu import APP_NAMES, CONFIGS

PLANS = {
    "bbv": SamplingPlan(stratifier=BBVClusters(), policy=Centroid()),
    "rfv": SamplingPlan(stratifier=RFVClusters(), policy=Centroid()),
    "rfv+rank": SamplingPlan(stratifier=RFVClusters(),
                             policy=RankedSetUnit()),
}


def main() -> None:
    arg = sys.argv[1] if len(sys.argv) > 1 else "557.xz_r"
    apps = tuple(APP_NAMES) if arg == "all" else (arg,)
    enable_compile_cache()
    engine = ExperimentEngine.auto()
    if engine.mesh is not None:
        print(f"# app axis sharded over {engine.mesh.devices.size} devices")

    # three batched sweeps: every app x config x plan estimate, served
    # through the shared region x config memo bank
    tables = {label: run_sweep(engine, SweepSpec(apps=apps, plan=plan))
              for label, plan in PLANS.items()}

    for app in apps:
        exp = engine.app(app)
        print(f"{app}: per-config CPI projection error (20 regions each)")
        print(f"{'config':8s} {'truth':>7s} {'SimPoint/BBV':>14s} "
              f"{'two-phase/RFV':>14s} {'RFV+ranked-set':>15s}")
        rows = {s: tables[s].filter(app=app) for s in tables}
        for i in range(len(CONFIGS)):
            rb = rows["bbv"].filter(config_index=i).rows[0]
            rr = rows["rfv"].filter(config_index=i).rows[0]
            rk = rows["rfv+rank"].filter(config_index=i).rows[0]
            print(f"config{i:2d} {rb.truth:7.3f} "
                  f"{rb.estimate:7.3f} ({rb.err_pct:4.1f}%) "
                  f"{rr.estimate:7.3f} ({rr.err_pct:4.1f}%) "
                  f"{rk.estimate:7.3f} ({rk.err_pct:4.1f}%)")
        print(f"simulation cost: {exp.sim.ledger.regions_simulated} region "
              f"simulations ({exp.sim.hits} cache hits)")


if __name__ == "__main__":
    main()
