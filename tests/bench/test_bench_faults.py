"""Each fault a cell can have, planted under the timed path, makes the
run come out not correct. The harness's look for a chip is skipped; the
rest of a run is driven as on the chip, at tiny sizes."""
from __future__ import annotations

import pytest

from _bench_cells import FL, MC, REPO, run_tiny, write_tiny


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return write_tiny(tmp_path_factory.mktemp("cells"))


def _faults():
    import sys
    sys.path.insert(0, str(REPO))
    from bench import faults
    return faults


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "answer_altered"])
def test_mc_fault_is_caught(tiny, monkeypatch, kind):
    monkeypatch.setattr(*_faults().mc(kind))
    res = run_tiny(tiny, MC, seconds=0.5)
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "answer_altered", "selection_altered",
                                  "half_client_batches"])
def test_fl_fault_is_caught(tiny, monkeypatch, kind):
    monkeypatch.setattr(*_faults().fl(kind))
    res = run_tiny(tiny, FL, seconds=0.5)
    assert res["correct"] is False, res["checks"]


def test_sound_runs_pass(tiny):
    for w in (MC, FL):
        assert run_tiny(tiny, w, seconds=0.5)["correct"] is True
