"""The placement reference and its control at a size a test run holds:
2,000 nodes of each configuration's cluster and one batch of its mix,
scheduled against the empty cluster by the reference itself. The
reference's own best choice reads no gap; the control (the same Filter
and Score computed in bfloat16) and first-feasible placement read a
share of gapped pods above the configuration's limit."""

import json
import os

import numpy as np
import pytest

from bench_tiny import BENCH

import generators
import placement
import reference

NODES, PODS = 2000, 1000
CELLS = [("fullgate-10k", "gated-backlog"),
         ("colocation-10k", "lsbe-backlog")]


def _load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def _gaps(config, traffic, choose, seed=7):
    """The gaps of the checked pods of one batch when each pod takes
    `choose(feasible, exact scores, bfloat16 scores)` against the empty
    cluster (no pod of the batch touches a node)."""
    cfg, tr = _load("configs", config), _load("traffic", traffic)
    cfg["cluster"]["params"]["num_nodes"] = NODES
    tr["backlog"] = PODS
    cluster = generators.make_cluster(cfg, seed)
    backlog = generators.make_backlog(tr, cfg["cluster"]["params"], seed + 1)
    state = reference.state_before(cluster, backlog, PODS, [])
    pod = {k: np.asarray(backlog[k]) for k in placement.POD_KEYS}
    scorer = placement.Scorer(cluster, backlog, state, cfg)
    idx = np.flatnonzero(placement.checked_pods(
        pod, np.zeros(PODS, int), np.full(PODS, -1), NODES))
    feasible = scorer.feasible(pod, idx)
    score = scorer.score(pod, idx)
    low = scorer.score(pod, idx, q=placement.bf16)
    pick = choose(feasible, score, low)
    rows = np.arange(idx.size)
    best = np.where(feasible, score, -np.inf).max(axis=1)
    have = feasible[rows, pick]
    return (best - score[rows, pick])[have], \
        cfg["guarantees"]["placement"]["gap"], \
        cfg["guarantees"]["limits"]["regret_share"]


def _argmax(feasible, values):
    return np.argmax(np.where(feasible, values, -np.inf), axis=1)


@pytest.mark.parametrize("config,traffic", CELLS)
def test_reference_choice_reads_no_gap(config, traffic):
    gaps, gap, _ = _gaps(config, traffic, lambda f, s, low: _argmax(f, s))
    assert gaps.size > 0.25 * PODS
    assert np.mean(gaps > gap) == 0.0


@pytest.mark.parametrize("config,traffic", CELLS)
def test_bf16_control_fails_the_limit(config, traffic):
    gaps, gap, limit = _gaps(config, traffic,
                             lambda f, s, low: _argmax(f, low))
    assert np.mean(gaps > gap) > limit


@pytest.mark.parametrize("config,traffic", CELLS)
def test_first_feasible_fails_the_limit(config, traffic):
    gaps, gap, limit = _gaps(config, traffic,
                             lambda f, s, low: np.argmax(f, axis=1))
    assert np.mean(gaps > gap) > limit
