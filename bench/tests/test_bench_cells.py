"""Whole runs of the harness on the CPU at a tiny size, with the look for a
chip skipped: the program agrees with the reference."""
import pytest


@pytest.mark.parametrize("cell,trace", [("mimic_cxr.train", 0),
                                        ("mimic_cxr.train", 1),
                                        ("uci_har.train", 1),
                                        ("mimic_cxr.serve", 0)])
def test_program_agrees_with_reference(harness, cell, trace):
    rc, res = harness(cell, trace)
    assert rc == 0 and res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "compared"
    assert res["device"]["platform"] == "cpu"
    if trace:
        assert "train_build_ms" in res["metrics"]
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert "setup_s" in res["metrics"] and len(res["metrics"]) >= 2
