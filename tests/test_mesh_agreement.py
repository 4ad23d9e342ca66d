"""``scripts/mesh_agreement.py compare``: what it reports as equal."""

import copy
import importlib.util
import pathlib

import pytest

SCRIPT = (pathlib.Path(__file__).resolve().parents[1] / "scripts"
          / "mesh_agreement.py")


@pytest.fixture(scope="module")
def agreement():
    spec = importlib.util.spec_from_file_location("mesh_agreement", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _record(agreement, layout):
    return {"layout": layout,
            "build": {f: f"sha-{f}" for f in agreement.BUILD_FIELDS},
            "studies": [{"seed": s, "digest": f"d{s}",
                         "stats": {"dg.count": f"c{s}", "dg.err_sum": f"e{s}"}}
                        for s in (1, 2)]}


def test_equal_records_compare_equal(agreement):
    one = _record(agreement, "none")
    assert agreement.compare(one, _record(agreement, "app2xtrial2")) == []


def test_each_difference_is_named(agreement):
    one = _record(agreement, "none")
    other = copy.deepcopy(one)
    other["build"]["truth"] = "other"
    other["studies"][1]["stats"]["dg.err_sum"] = "other"
    other["studies"][1]["digest"] = "other"
    assert agreement.compare(one, other) == [
        "build truth", "seed 2 dg.err_sum", "seed 2 digest"]
    other["studies"][0]["seed"] = 3
    assert agreement.compare(one, other)[-1] == \
        "the two files ran other study seeds"
