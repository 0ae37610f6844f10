"""The planted faults on the colocation cell at a tiny size on the CPU:
a sound run is correct and each fault comes out not correct (see
test_bench_faults_fullgate for what each one breaks)."""

import pytest

from bench_tiny import reading


@pytest.mark.parametrize("kind", ["sound", "bf16_requests", "score_dropped",
                                  "state_unchanged", "half_batch",
                                  "answer_altered"])
def test_colocation_faults(checkout, kind):
    result = reading(checkout, "tiny-colocation.lsbe", kind)
    assert result["correct"] == (kind == "sound"), result["checks"]
