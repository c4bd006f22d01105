"""Both drivers end to end at a tiny preset on the CPU: the last line has
the contract's keys, the cell's end-to-end metrics, and ``correct``."""
import pytest

from presets import drive

E2E = {"resnet50-fit-synth": {"train_samples_per_s", "setup_s"},
       "bloom1b7-saturated": {"output_tokens_per_s", "setup_s"},
       "open-loop": {"output_tokens_per_s", "setup_s"}}


@pytest.mark.parametrize("cell", sorted(E2E))
def test_last_line(cell):
    out = drive(cell)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "compared"
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == E2E[cell]
    for m in out["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(out["device"])
    for name, c in out["compared"].items():
        assert c["limit"] is None or c["value"] <= c["limit"], name
