"""Every cell of BENCHMARK.json resolves to files of its own, and the file
keeps the benchmark's format."""
import json
import re

import pytest

from bench import common
from bench.tests.conftest import RESOLVE, with_pending

SPEC = json.loads((common.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", [w["name"] for w in with_pending()["workloads"]])
def test_cell_resolves_to_its_files(cell):
    info = RESOLVE(cell)
    assert info["config"]["name"] == info["cell"]["config"]
    assert info["traffic"]["kind"] in ("train", "serve")
    assert info["limits"] and all(v > 0 for v in info["limits"].values())
    names = {m["name"] for m in info["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert info["per_layer"]
    for m in info["per_layer"]:
        assert callable(common.reader(m["name"]))


def test_names_units_and_keys():
    spec = with_pending()
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        cfg = json.loads((common.ROOT / c["file"]).read_text())
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"])
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert all(m["moves"] in e2e for m in spec["per_layer"])


def test_peaks_table_refuses_unknown_kind():
    assert common.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        common.peaks_for("cpu")


def test_compare_fails_missing_and_nan():
    ok, out = common.compare({"a": 1.0, "b": float("nan")}, {"a": 2.0, "b": 1.0})
    assert not ok and out["a"] == {"value": 1.0, "limit": 2.0}
    assert not common.compare({}, {"a": 1.0})[0]
    assert common.compare({"a": 0.5}, {"a": 1.0})[0]
