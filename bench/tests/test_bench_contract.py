"""The benchmark's files against its own contract (CPU only, no chip)."""

from __future__ import annotations

import json
import pathlib
import re
import shutil

import pytest

from bench.lib import peaks, registry, roofline

BENCH = registry.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _names():
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[kind]:
            yield kind, e["name"]


@pytest.mark.parametrize("kind,name", list(_names()))
def test_names_use_allowed_characters(kind, name):
    assert NAME.match(name), (kind, name)


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert (registry.BENCH_DIR / "metrics" / f"{metric['name']}.py").is_file()
    if "moves" in metric:
        moved = next(m for m in BENCH["end_to_end"]
                     if m["name"] == metric["moves"])
        cells = metric.get("workloads",
                           [w["name"] for w in BENCH["workloads"]])
        for cell in cells:
            assert moved in registry.cell_metrics(BENCH, cell, "end_to_end")
    else:
        assert 0.01 <= metric["bound"] <= 0.25


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_workload_names_existing_config_and_driver(cell):
    resolved = registry.resolve_cell(BENCH, cell["name"])
    wl = resolved["workload"]
    assert (registry.BENCH_DIR / "drivers" / f"{wl['driver']}.py").is_file()
    assert resolved["config_entry"]["file"] == \
        f"bench/configs/{wl['config']}.json"
    assert cell["chips"] in (1, 4)
    e2e = registry.cell_metrics(BENCH, cell["name"], "end_to_end")
    assert {"setup_s"} < {m["name"] for m in e2e}
    assert registry.cell_metrics(BENCH, cell["name"], "per_layer")


def test_every_config_is_used_and_lists_its_cuts():
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["name"] in used
        cfg = registry.config(c["name"])
        assert set(c["reduced"]) <= set(cfg)


def test_new_files_are_picked_up_without_edits(tmp_path):
    """A configuration, a cell, a driver and a metric added as files."""
    root = tmp_path / "checkout"
    shutil.copytree(registry.BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((registry.ROOT / "BENCHMARK.json").read_text())
    bdir = root / "bench"
    cfg = registry.config("spec17int_k20")
    cfg["num_strata"] = 30
    (bdir / "configs" / "fixture_k30.json").write_text(json.dumps(cfg))
    (bdir / "drivers" / "fixture.py").write_text(
        "SPANS = ()\ndef request(state, seed, i):\n"
        "    return {'work': 1, 'out': i}\n")
    (bdir / "metrics" / "fixture_rate.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    (bdir / "workloads" / "fixture.k30.json").write_text(json.dumps(dict(
        name="fixture.k30", config="fixture_k30", chips=1,
        driver="fixture", traffic="fixture_mix", span="study", params={},
        why="fixture")))
    bench["configs"].append(dict(name="fixture_k30", source="fixture",
                                 file="bench/configs/fixture_k30.json",
                                 reduced=[], why="fixture"))
    bench["workloads"].append(dict(name="fixture.k30", config="fixture_k30",
                                   traffic="fixture_mix", chips=1,
                                   why="fixture"))
    bench["per_layer"].append(dict(
        name="fixture_rate", unit="1/s", better="higher",
        source="host_clock", layer="fixture", moves="setup_s",
        workloads=["fixture.k30"]))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    b = registry.benchmark(root)
    cell = registry.resolve_cell(b, "fixture.k30", bdir)
    assert cell["config"]["num_strata"] == 30
    drv = registry.driver(cell["workload"]["driver"], bdir)
    assert drv.request(None, 0, 3) == {"work": 1, "out": 3}
    names = [m["name"] for m in registry.cell_metrics(b, "fixture.k30",
                                                       "per_layer")]
    assert "fixture_rate" in names
    assert registry.metric("fixture_rate", bdir).read({}) == 42.0
    # the new per-layer metric is not reported by the existing cells
    assert "fixture_rate" not in [
        m["name"] for m in registry.cell_metrics(b, "trials_100k.k20",
                                                  "per_layer")]


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        registry.resolve_cell(BENCH, "no_such_cell")


def test_peaks_are_keyed_by_device_kind():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_kmeans_assign_counts_at_a_small_shape():
    # 2 problems of 5 points in 3 dims against 4 centroids
    ops, nbytes = roofline.kmeans_assign_counts(n=[5, 5], k=4, d=3)
    assert ops == 3 * 2 * 5 * 4 * 3                      # 360
    assert nbytes == 4 * 2 * (5 * 3 + 4 * 3 + 2 * 5)     # 296
    t, bound = roofline.least_time(ops, nbytes, 1e3, 1e3)
    assert (t, bound) == (0.36, "compute")
    t, bound = roofline.least_time(ops, nbytes, 1e6, 1e3)
    assert (t, bound) == (0.296, "memory")
    # ragged problems count their own points, not the stack's padding
    ops, nbytes = roofline.kmeans_assign_counts(n=[5, 2], k=4, d=3)
    assert ops == 3 * 7 * 4 * 3
    assert nbytes == 4 * (7 * 3 + 2 * 4 * 3 + 2 * 7)


def test_command_and_paths_stay_inside_the_benchmark():
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    for p in BENCH["paths"]:
        assert (registry.ROOT / p).is_dir()
    assert 1 <= BENCH["run_seconds"] <= 51
    for c in BENCH["configs"]:
        assert pathlib.PurePosixPath(c["file"]).parts[0] == "bench"


def test_free_text_fields_fit_one_line():
    texts = [c["source"] for c in BENCH["configs"]]
    texts += [e["why"] for k in ("configs", "workloads") for e in BENCH[k]]
    texts += [m["layer"] for m in BENCH["per_layer"]]
    texts += BENCH["command"]
    for t in texts:
        assert 1 <= len(t) <= 200 and "\n" not in t and "\t" not in t, t
    for c in BENCH["configs"]:
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    assert len(json.dumps(BENCH)) <= 64 * 1024
