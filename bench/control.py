#!/usr/bin/env python3
"""Readings that a cell's limits are set from, in one process.

    python3 bench/control.py --workload trials_100k.k20 --seeds 12 \
        --controls 3 --first-seed 4000000000 --out readings.json

Builds the cell's bank once. For each seed it sends the requests that a
run with that seed sends first (``--requests`` of them), through the
program's own timed path, and compares them with the plain reference:
the lower readings, sound runs of the program. For the first
``--controls`` seeds the control takes the program's place: the same
reference computed in bfloat16, the precision below the program's
float32, on the chip: the upper readings. The build's numbers are read
once, the control's from a bfloat16 perf model, nearest-centroid
assignment and baseline order. Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench.lib import bank, registry  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=4_000_000_000)
    ap.add_argument("--requests", type=int, default=None,
                    help="requests per seed (default: the cell's "
                    "check_studies, else 100)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from bench import run

    bench = registry.benchmark()
    cell = registry.resolve_cell(bench, args.workload)
    devs = run.find_devices(int(cell["entry"]["chips"]))
    run.enable_cache()
    config, params = cell["config"], cell["workload"]["params"]
    drv = registry.driver(cell["workload"]["driver"])
    n_req = args.requests or int(params.get("check_studies", 100))
    t0 = time.perf_counter()
    ctx = dict(config=config, params=params, seed=0, devices=devs,
               clock=time.perf_counter)
    out = {"workload": args.workload, "requests": n_req, "program": [],
           "control": []}
    with jax.default_device(devs[0]):
        state = drv.setup(ctx)
        drv.warm(state)
        prog = drv.extract(state)
        ref = bank.reference_build(config, prog["apps"])
        out["build_program"] = bank.build_readings(prog, ref)
        out["build_control"] = bank.control_build_readings(config, prog, ref)
        print("build", out["build_program"], out["build_control"], flush=True)
        for k in range(args.seeds):
            seed = args.first_seed + k
            if hasattr(drv, "rewind"):
                drv.rewind(state)
            outputs = [drv.request(state, seed, i)["out"]
                       for i in range(n_req)]
            prog = drv.extract(state)
            g = drv.readings(config, params, prog, ref, outputs, seed)
            out["program"].append(dict(seed=seed, **g))
            print("program", seed, g, flush=True)
            if k < args.controls:
                gc = drv.readings(config, params, prog, ref, outputs, seed,
                                  dtype=jnp.bfloat16)
                out["control"].append(dict(seed=seed, **gc))
                print("control", seed, gc, flush=True)
    out["seconds"] = time.perf_counter() - t0
    text = json.dumps(out, indent=1)
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(text)
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
