"""The planted faults on the full-gate cells, driven through the harness
at a tiny size on CPU devices (the harness's look for a chip skipped): a
sound run is correct, and every fault the cell can have comes out not
correct: requests rounded to bfloat16 before the program sees them, the
LoadAware score dropped (first-feasible placement), a cycle that
commits nothing, half of each batch left out, a binding altered where
it is produced, and on four chips the exchange between chips left
out."""

import pytest

from bench_tiny import reading


def test_sound_run_is_correct(checkout):
    result = reading(checkout, "tiny-fullgate.gated", "sound")
    assert result["correct"], result["checks"]
    assert result["checks"]["state_gap"]["value"] == 0.0
    assert result["checks"]["violations"]["value"] == 0
    assert result["placement"]["placement_checked"] > 0


@pytest.mark.parametrize("kind", ["bf16_requests", "score_dropped",
                                  "state_unchanged", "half_batch",
                                  "answer_altered"])
def test_faults_are_not_correct(checkout, kind):
    result = reading(checkout, "tiny-fullgate.gated", kind)
    assert not result["correct"], result["checks"]


def test_score_dropped_fails_the_placement_check(checkout):
    result = reading(checkout, "tiny-fullgate.gated", "score_dropped")
    regret = result["checks"]["regret_share"]
    assert regret["value"] > regret["limit"], result["checks"]


def test_no_exchange_between_chips_is_not_correct(checkout):
    result = reading(checkout, "tiny-fullgate.gated.4chip", "no_exchange")
    assert not result["correct"], result["checks"]
    assert result["checks"]["state_gap"]["value"] > 0.01
