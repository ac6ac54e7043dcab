"""chip_smoke.py's phases at reduced size on the CPU, and its refusal to
run anywhere but on a TPU."""
import json

import numpy as np
import pytest

import chip_smoke


@pytest.fixture
def meter():
    m = chip_smoke.CompileMeter()
    yield m
    m.close()


def test_main_refuses_cpu(capsys):
    assert chip_smoke.main() == 1
    last = capsys.readouterr().out.strip().splitlines()[-1]
    doc = json.loads(last)
    assert doc["ok"] is False and doc["device"]["platform"] == "cpu"


def test_serving_phase_reduced(meter, capsys):
    chip_smoke.serving_phase(
        ["--arch", "phi3-mini-3.8b", "--reduced", "--requests", "4",
         "--slots", "2", "--max-len", "64", "--max-new", "4",
         "--shared-prefix", "16", "--prefill-chunk", "8"], meter)
    out = capsys.readouterr().out
    assert out.count("[check] 4 requests done") == 2
    assert "tokens match the sequential reference" in out
    assert "[check] paged == contiguous" in out
    assert "[time] serving/paged serve.main" in out


def test_simt_phase_reduced(meter, capsys):
    chip_smoke.simt_phase(("vecadd",), (2, 2), meter)
    out = capsys.readouterr().out
    assert "simt/vecadd 2w2t: oracle ok" in out
    assert out.count("[time] simt/vecadd 2w2t") == 2


def test_simt_phase_rejects_changed_stats(meter, tmp_path):
    with open(chip_smoke.SIMT_BASELINE) as f:
        doc = json.load(f)
    doc["vecadd/2w2t"]["stats"]["cycles"] += 1
    bad = tmp_path / "baseline.json"
    bad.write_text(json.dumps(doc))
    with pytest.raises(AssertionError, match="cycles"):
        chip_smoke.simt_phase(("vecadd",), (2, 2), meter, baseline=str(bad))


def test_reference_check_allows_only_exact_ties():
    rows = np.array([[1.0, 3.0, 3.0, 0.0],
                     [2.0, 0.5, 1.0, 0.0]], np.float32)
    assert chip_smoke.check_against_reference(rows, [1, 0], "r") == 0
    assert chip_smoke.check_against_reference(rows, [2, 0], "r") == 1
    with pytest.raises(AssertionError, match="step 1"):
        chip_smoke.check_against_reference(rows, [1, 2], "r")
