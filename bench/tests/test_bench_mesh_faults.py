"""The four-chip cell's output check catches the faults a mesh adds.

CPU only, in a child with four CPU devices (a 2 x 2 ``("app", "trial")``
mesh, as the cell's configuration asks), at two apps of the bank and
512-trial studies. The child drives the rest of a benchmark run (the
look for a chip waived) soundly, then with the trial-axis ``psum`` left
out, then with the census computed app-sharded without the perf model's
pin of its app count (one app per device, as before the pin): a number
the sound run keeps within its limit goes over it.
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]

_CHILD = r"""
import json

import jax

from bench import run
from bench.lib import registry

CELL = "trials_100k.k20.x4"


def small_cell():
    cell = registry.resolve_cell(registry.benchmark(), CELL)
    cell["config"]["apps"] = ["505.mcf_r", "520.omnetpp_r"]
    cell["workload"]["params"].update(trials=512, check_studies=1)
    return cell


def run_once():
    args = run.parse_args(["--workload", CELL, "--seed", "3000000007",
                           "--seconds", "0.2", "--trace", "0"])
    res = run.run_cell(args, cell=small_cell(), require_accelerator=False,
                       log=lambda msg: None)
    return {"correct": res["correct"], "device": res["device"],
            "checks": res["checks"]}


from repro.experiments import montecarlo
from repro.simcpu import perfmodel

out = {"sound": run_once()}
psum = jax.lax.psum
jax.lax.psum = lambda x, axis_name, **kw: x
montecarlo._streaming_program.cache_clear()
out["no_psum"] = run_once()
jax.lax.psum = psum
montecarlo._streaming_program.cache_clear()
pin = perfmodel._pin_apps
perfmodel._pin_apps = lambda x, mesh: x
out["unpinned"] = run_once()
perfmodel._pin_apps = pin

drv = registry.driver("trials_mesh")
ctx = dict(config=small_cell()["config"], params={}, devices=jax.devices()[:3],
           clock=run.clock)
try:
    drv.setup(ctx)
    out["three_devices"] = "built"
except ValueError as e:
    out["three_devices"] = str(e)
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   p for p in (str(ROOT / "src"),
                               os.environ.get("PYTHONPATH")) if p))
    out = subprocess.run([sys.executable, "-c", _CHILD], cwd=ROOT,
                         capture_output=True, text=True, timeout=900,
                         env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    line = next(ln for ln in out.stdout.splitlines()
                if ln.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


def caught(sound, faulted):
    """Numbers a sound run keeps within the limit and the fault breaks."""
    return [k for k, c in faulted["checks"].items()
            if c["value"] > c["limit"]
            and sound["checks"][k]["value"] <= sound["checks"][k]["limit"]]


def test_sound_sharded_run_passes_the_check(runs):
    sound = runs["sound"]
    assert sound["correct"], sound["checks"]
    assert sound["device"]["count"] == 4
    assert sound["checks"]["count_gap"]["value"] == 0
    assert sound["checks"]["census_mismatch"]["value"] == 0


def test_trial_axis_psum_left_out_is_caught(runs):
    res = runs["no_psum"]
    assert not res["correct"]
    assert res["checks"]["count_gap"]["value"] > 0
    assert "count_gap" in caught(runs["sound"], res)


def test_census_off_one_device_is_caught(runs):
    res = runs["unpinned"]
    assert not res["correct"]
    assert res["checks"]["census_mismatch"]["value"] > 0
    assert "census_mismatch" in caught(runs["sound"], res)


def test_devices_that_do_not_make_the_mesh_are_refused(runs):
    assert "app2xtrial1" in runs["three_devices"]
    assert "app2xtrial2" in runs["three_devices"]
