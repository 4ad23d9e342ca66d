#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

In order: set-up (imports, the cell's driver builds its state, warm-up
of every shape the window sends, programs from the persistent compile
cache at ``<checkout>/.jax_cache``), then a window of whole requests
back to back until ``--seconds`` have passed, then the output check
against the plain reference. The last line of stdout is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and,
with ``--trace 1``, ``breakdown``); earlier lines report compile
accounting and a digest of the window's outputs, and the last lines of
stderr give every number compared beside its limit.

With ``--trace 0`` the metrics are the cell's end-to-end metrics. With
``--trace 1`` the profiler records the whole run up to the end of the
window, and the metrics are the cell's per-layer metrics.

Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero before any work and prints no result.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench.lib import registry  # noqa: E402
from bench.lib.compile_listener import CompileCounter  # noqa: E402


def clock() -> float:
    return time.perf_counter()


class NoAccelerator(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def find_devices(chips: int, require_accelerator: bool = True) -> list:
    """The first ``chips`` devices; without a TPU (or too few chips)
    exits non-zero unless the caller waived the accelerator."""
    import jax

    devs = jax.devices()
    if require_accelerator:
        if devs[0].platform != "tpu":
            raise NoAccelerator(f"bench/run.py: needs a TPU; JAX found "
                                f"platform {devs[0].platform!r}")
        if len(devs) < chips:
            raise NoAccelerator(f"bench/run.py: the cell needs {chips} chips;"
                                f" JAX found {len(devs)}")
    return devs[:chips]


def enable_cache() -> None:
    """The persistent compile cache at the checkout's fixed path (or
    ``$JAX_COMPILATION_CACHE_DIR``), every program cached."""
    import jax

    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def peak_bytes(devs) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(args, *, cell=None, require_accelerator: bool = True,
             log=None) -> dict:
    """One run of one cell; returns the result object (not printed).

    Tests pass a resized ``cell`` and waive the accelerator; the look
    for a chip is the only step they skip."""
    log = log or (lambda msg: print(msg, flush=True))
    bench = registry.benchmark()
    cell = cell or registry.resolve_cell(bench, args.workload)
    wl, config = cell["workload"], cell["config"]
    chips = int(cell["entry"]["chips"])
    devs = find_devices(chips, require_accelerator)

    import jax

    if require_accelerator:
        # a rehearsal off the chip leaves JAX's persistent cache alone
        enable_cache()
    driver = registry.driver(wl["driver"])
    counter = CompileCounter().install()
    trace_dir = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        # the drivers' spans and the device, without a trace of every
        # Python call: that floods the host tracer's buffer in set-up,
        # and the window's spans are lost
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)

    ctx = dict(config=config, params=wl["params"], seed=args.seed,
               devices=devs, clock=clock)
    with jax.default_device(devs[0]):
        with jax.profiler.TraceAnnotation("setup"):
            state = driver.setup(ctx)
            with jax.profiler.TraceAnnotation("setup.warm"):
                driver.warm(state)
        setup_s = clock() - _T0
        setup_compiles, setup_compile_s = counter.snapshot()
        log(f"set-up: {setup_s:.3f} s, {setup_compiles} programs compiled "
            f"in {setup_compile_s:.3f} s")

        requests, outputs = [], []
        w0 = clock()
        with jax.profiler.TraceAnnotation("window"):
            i = 0
            while clock() - w0 < args.seconds:
                r0 = clock()
                with jax.profiler.TraceAnnotation(wl["span"]):
                    res = driver.request(state, args.seed, i)
                requests.append((r0 - w0, clock() - w0, res["work"]))
                outputs.append(res["out"])
                i += 1
        window_s = clock() - w0
    if trace_dir:
        jax.profiler.stop_trace()
    window_compiles = counter.snapshot()[0] - setup_compiles
    log(f"window: {len(requests)} requests in {window_s:.3f} s, "
        f"{window_compiles} programs compiled inside the window")
    log(f"digest of the window's outputs: {driver.digest(outputs)}")
    mem = peak_bytes(devs)

    build_s, kernels = state.get("build_s"), state.get("kernels")
    prog = driver.extract(state)
    del state
    gc.collect()
    with jax.default_device(devs[0]):
        checks = driver.check(config, wl["params"], prog, outputs, args.seed)
    correct = all(v <= lim for _, v, lim in checks)

    mctx = dict(setup_s=setup_s, window_s=window_s, requests=requests,
                build_s=build_s, compile_s=setup_compile_s,
                compiles=setup_compiles, window_compiles=window_compiles,
                config=config, params=wl["params"], cell=args.workload,
                trace=None, device_kind=devs[0].device_kind,
                kernels=kernels)
    breakdown, busy_s, traced_window_s = None, None, None
    kind = "per_layer" if args.trace else "end_to_end"
    if trace_dir:
        from bench.trace import reduce

        ev = reduce.load_events(trace_dir, driver.SPANS)
        mctx["trace"] = ev
        win = reduce.span(ev, "window")
        if win:
            lo, hi = win
            busy_s = reduce.busy_ns(ev, lo, hi) / 1e9
            traced_window_s = (hi - lo) / 1e9
            breakdown = {"device_ops": reduce.top_ops(ev, lo, hi),
                         "idle_gaps": reduce.idle_gaps(ev, lo, hi)}
        shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = {}
    for m in registry.cell_metrics(bench, args.workload, kind):
        v = registry.metric(m["name"]).read(mctx)
        if v is None:
            log(f"metric {m['name']}: nothing to read in this run")
        else:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    if args.trace:
        device["busy_s"] = busy_s
        device["window_s"] = traced_window_s
    result = {"correct": bool(correct), "attempted": len(requests),
              "failed": 0, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    # a number that could not be read prints as the largest float
    result["checks"] = {k: {"value": v if math.isfinite(v) else 1e308,
                            "limit": lim} for k, v, lim in checks}
    return result


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        result = run_cell(args)
    except NoAccelerator as e:
        print(str(e), file=sys.stderr)
        return 3
    for k, c in result["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {k}: {c['value']!r} limit {c['limit']!r} {ok}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
