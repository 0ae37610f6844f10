"""The benchmark's copied generators stay the yardstick: every column
they produce at fixed seeds is pinned to the digests kept in
benchmark/digests.json (never compared with the program's own
generator, which a later PR may change)."""

import hashlib
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import generators  # noqa: E402

DIGESTS = os.path.join(ROOT, "benchmark", "digests.json")

# (name, callable) at small sizes and fixed seeds, one large seed each
CASES = {
    "full_gate_cluster": lambda: generators.full_gate_cluster(
        200, seed=7, num_quotas=32, num_gangs=64),
    "full_gate_pods": lambda: generators.full_gate_pods(
        1000, 200, seed=3_000_000_008, num_quotas=32, num_gangs=64),
    "synthetic_cluster": lambda: generators.synthetic_cluster(
        200, seed=3_000_000_007, num_quotas=32, num_gangs=64),
    "synthetic_pods": lambda: generators.synthetic_pods(
        1000, seed=8, num_quotas=32, num_gangs=64),
}


def column_digests(tree, prefix=""):
    out = {}
    for key in sorted(tree):
        value = tree[key]
        if isinstance(value, dict):
            out.update(column_digests(value, f"{prefix}{key}."))
        else:
            a = np.ascontiguousarray(np.asarray(value))
            h = hashlib.sha256(f"{a.dtype}{a.shape}".encode())
            h.update(a.tobytes())
            out[prefix + key] = h.hexdigest()[:16]
    return out


@pytest.mark.parametrize("name", sorted(CASES))
def test_generator_columns_match_pinned_digests(name):
    with open(DIGESTS) as f:
        pinned = json.load(f)[name]
    assert column_digests(CASES[name]()) == pinned


def test_same_seed_same_backlog_other_seed_other_backlog():
    a = generators.full_gate_pods(512, 64, seed=2**31 + 5)
    b = generators.full_gate_pods(512, 64, seed=2**31 + 5)
    c = generators.full_gate_pods(512, 64, seed=2**31 + 6)
    assert column_digests(a) == column_digests(b)
    assert column_digests(a)["requests"] != column_digests(c)["requests"]
