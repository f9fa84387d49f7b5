"""A whole run of each cell at a small size on the CPU, with the served
path broken underneath: ``correct`` comes out false for each fault the
cell can have, and true without one. The look for a chip is the only
step left out (the run reports the CPU as its device).

Faults (see ``serve_child.py``): ``stale`` answers an incremental scan
without scanning (state returned unchanged), ``half`` leaves every
second report fold or review out, ``flip`` alters an answer where it is
produced, and ``audit`` is the admission cell's control: the Enforce
policies served under Audit. One chip, so no exchange between chips.
"""

import pytest

from harness import core

SCAN = "pss-restricted.scan-rollout"
ADMISSION = "pss-restricted-enforce.admission-steady"
SMALL = {
    SCAN: ({"pods": 400}, {"wave_pods": 50, "check_sample": 50}, 3.0),
    ADMISSION: ({"pods": 2000}, {"rate_per_s": 20, "warm_seconds": 1}, 4.0),
}
# the checks that must catch each fault
CAUGHT_BY = {
    "stale": {"mismatched_pods", "missing_pods"},
    "half": {"mismatched_pods", "missing_pods", "mismatched_reviews"},
    "flip": {"mismatched_pods", "mismatched_reviews"},
    "audit": {"mismatched_reviews"},
}


def run_small(cell_name, fault, seed=266486846):
    cell = core.Cell(cell_name, core.load_spec(held=True))
    cluster, traffic, seconds = SMALL[cell_name]
    cell.config["cluster"].update(cluster)
    cell.traffic.update(traffic)
    run = core.run_cell(cell, seed, seconds, False, fault)
    run.cleanup()
    return run


@pytest.mark.parametrize("cell,fault", [
    (SCAN, None), (SCAN, "stale"), (SCAN, "half"), (SCAN, "flip"),
    (ADMISSION, None), (ADMISSION, "audit"), (ADMISSION, "half"),
    (ADMISSION, "flip"),
])
def test_correct_is_false_exactly_under_a_fault(cell, fault):
    run = run_small(cell, fault)
    failed = {n for n, c in run.checks.items.items() if c["value"] > c["limit"]}
    assert run.checks.items["exceptions"]["value"] == 0
    if fault is None:
        assert run.checks.correct(), failed
    else:
        assert not run.checks.correct()
        assert failed and failed <= CAUGHT_BY[fault], failed
