"""The SIMT cell's whole run at small kernel sizes on the CPU (the
harness's look for a chip skipped): the independent reference agrees
with every launch, and `correct` comes out false with the machine broken
underneath:

- an answer altered where it is produced: every word of data memory
  after a run is off by one;
- a step that returns its state unchanged: the run hands back the state
  it was given, so no kernel writes its output.

(Half of a batch and the exchange between chips have no place in a
one-chip simulator.)"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchmarks.chip import small  # noqa: E402


def test_clean_run_is_correct():
    res = small.run_simt()
    assert res["correct"], res["compared"]
    assert res["compared"]["kernels_checked"]["value"] == 7
    assert res["metrics"]["sim_instrs_per_s"]["value"] > 0
    assert list(res)[-1] == "compared"


def _altered(run):
    def wrapped(*a, **k):
        st = run(*a, **k)
        return st._replace(dmem=st.dmem + 1)
    return wrapped


def _unchanged(run):
    from repro.core.simt import machine

    def wrapped(mc, program, dmem_image=None, state=None, label=None):
        return state if state is not None else machine.init_state(
            mc, dmem_image)
    return wrapped


@pytest.mark.parametrize("seed", [1, 2, 2**33 + 3])
def test_control_fails_at_the_cells_sizes(seed):
    """The control, the reference with its float outputs in bfloat16, at
    the Fig. 9 sizes the cell runs: every float kernel's launch is off
    (the upper reading of `mismatched_launches`, limit 0), every
    integer kernel's matches, and the float32 reference itself matches."""
    from benchmarks.chip import bench, traffic
    ref = bench.load_module("reference/vortex_simt.py")
    cfg = bench.load_json(os.path.join(bench.HERE, "configs",
                                       "vortex-8w8t.json"))
    mix = bench.load_json(os.path.join(bench.HERE, "traffic",
                                       "rodinia_fig9.json"))
    off = set()
    for spec in traffic.launches(mix, seed, 1):
        args = (spec["name"], spec["kwargs"], spec["data_seed"])
        ctl = ref.control_dmem(*args, cfg["dmem_words"])
        if ref.mismatches(*args, ctl) > 0:
            off.add(spec["name"])
        addr, want, _ = ref.KERNELS[spec["name"]](spec["data_seed"],
                                                  **spec["kwargs"])
        exact = np.zeros(cfg["dmem_words"], np.int32)
        exact[addr // 4: addr // 4 + want.size] = want.view(np.int32)
        assert ref.mismatches(*args, exact) == 0
    assert off == {"saxpy", "sgemm", "gaussian", "nn"}


@pytest.mark.parametrize("fault", [_altered, _unchanged],
                         ids=["answer_altered", "state_unchanged"])
def test_broken_machine_is_not_correct(monkeypatch, fault):
    from repro.core.simt import machine
    monkeypatch.setattr(machine, "run", fault(machine.run))
    res = small.run_simt(seconds=1.0)
    assert not res["correct"]
    assert res["compared"]["mismatched_launches"]["value"] > 0
