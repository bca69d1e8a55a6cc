"""The traffic generator: deterministic in the seed, and the same work
for every seed."""

import json
from collections import Counter

import numpy as np
import pytest

from perfbench import manifest, traffic

SEEDS = (0, 7, 2 ** 31 + 11)


@pytest.fixture(scope="module")
def spec():
    return json.loads((manifest.BENCH_DIR / "traffic"
                       / "serve-chat-steady.json").read_text())


def test_same_seed_same_cycle(spec):
    a = traffic.serve_cycle(spec, 50257, 123)
    b = traffic.serve_cycle(spec, 50257, 123)
    assert a == b


def test_every_seed_offers_the_same_pairs_and_tokens(spec):
    grid = Counter(traffic.length_grid(spec))
    offered = sum(o for _, o in traffic.length_grid(spec))
    orders = []
    for seed in SEEDS:
        cycle = traffic.serve_cycle(spec, 50257, seed)
        assert Counter((len(r.prompt), r.max_tokens) for r in cycle) == grid
        assert sum(r.max_tokens for r in cycle) == offered
        due = [r.due_s for r in cycle]
        assert due == sorted(due)
        assert 0 <= due[0] and due[-1] < traffic.cycle_seconds(spec)
        orders.append([(len(r.prompt), r.max_tokens) for r in cycle])
    assert orders[0] != orders[1]            # the seed does decide the order


def test_grid_is_inside_the_files_limits(spec):
    p, o = spec["prompt_tokens"], spec["output_tokens"]
    for n_prompt, n_out in traffic.length_grid(spec):
        assert p["lo"] <= n_prompt <= p["hi"] and o["lo"] <= n_out <= o["hi"]
        assert n_prompt + n_out <= spec["max_context"]
    assert len(traffic.length_grid(spec)) == \
        spec["prompt_quantiles"] * spec["output_quantiles"]


def test_quantiles_are_of_the_stated_distribution():
    dist = {"median": 100, "sigma": 0.5, "lo": 1, "hi": 10 ** 6}
    q = traffic._lognormal_quantiles(dist, 5)
    assert q[2] == 100 and q == sorted(q)
    # symmetric in log space about the median
    assert abs(q[0] * q[4] - 100 * 100) / 1e4 < 0.02


def test_schedule_plays_the_cycle_round(spec):
    cycle = traffic.serve_cycle(spec, 50257, 5)
    period = traffic.cycle_seconds(spec)
    plan = traffic.schedule(cycle, period, warm_s=0.5 * period,
                            window_s=period)
    inside = [r for r in plan if r.due_s >= 0]
    warm = [r for r in plan if r.due_s < 0]
    assert [r.prompt for r in inside] == [r.prompt for r in cycle]
    # the warm-up is the end of the cycle before
    tail = [r for r in cycle if r.due_s >= 0.5 * period]
    assert [r.prompt for r in warm] == [r.prompt for r in tail]
    assert all(-0.5 * period <= r.due_s < period for r in plan)


def test_rate_scale_only_squeezes_time(spec):
    a = traffic.serve_cycle(spec, 50257, 9, rate_scale=1.0)
    b = traffic.serve_cycle(spec, 50257, 9, rate_scale=2.0)
    assert [r.prompt for r in a] == [r.prompt for r in b]
    assert np.allclose([r.due_s for r in a], [2 * r.due_s for r in b])


def test_train_batches_from_the_seed():
    spec = {"ring": 3, "batch": 2, "seq": 8}
    a = traffic.train_batches(spec, 100, 2 ** 31 + 5)
    b = traffic.train_batches(spec, 100, 2 ** 31 + 5)
    c = traffic.train_batches(spec, 100, 6)
    assert a.shape == (3, 2, 9) and a.dtype == np.int32
    assert (a == b).all() and (a != c).any()
    assert a.min() >= 0 and a.max() < 100
    assert len({x.tobytes() for x in a}) == 3      # distinct batches


def test_a_pair_that_cannot_fit_is_refused(spec):
    bad = {**spec, "max_context": 100}
    with pytest.raises(ValueError):
        traffic.length_grid(bad)


def test_prompt_tokens_come_from_the_seed_alone(spec):
    a = traffic.serve_cycle(spec, 50257, 2 ** 31 + 11)
    b = traffic.serve_cycle(spec, 50257, 2 ** 31 + 11)
    c = traffic.serve_cycle(spec, 50257, 2 ** 31 + 12)
    assert [r.prompt for r in a] == [r.prompt for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in c]
    assert all(0 <= t < 50257 for r in a for t in r.prompt)
    assert traffic.key_seed(2 ** 31 + 11) < 2 ** 31


def _serving_cells():
    bench = manifest.load_manifest()
    cells = [manifest.load_cell(bench, w["name"]) for w in bench["workloads"]]
    return [(c["name"], c["traffic_file"], bench["run_seconds"])
            for c in cells if c["traffic_file"]["kind"] == "serve"]


@pytest.mark.parametrize("name,spec,seconds", _serving_cells(),
                         ids=[c[0] for c in _serving_cells()])
def test_every_seed_has_arrivals_in_the_traced_stretch(name, spec, seconds):
    """A traced run captures the last ``trace_seconds`` of the window, and
    a capture with no device operation in it is refused (PR 38: XL's
    steady mix traced 10 s of 51, and seed 798041194's last request was due
    at 39.6 s).  Arrival times need no token ids: the generator's stream."""
    period = traffic.cycle_seconds(spec)
    lead = seconds - min(spec["trace_seconds"], seconds)
    n = len(traffic.length_grid(spec))
    for seed in list(range(400)) + [798041194, 2 ** 31 + 11]:
        due = np.sort(traffic.rng_for(seed, "serve_arrivals")
                      .uniform(0.0, period, n))
        times = np.concatenate([k * period + due for k in
                                range(int(seconds // period) + 1)])
        # a second of room: the capture starts a little late, and a request
        # that arrives as it closes decodes outside it
        inside = (times >= lead + 1.0) & (times < seconds - 1.0)
        assert inside.any(), (name, seed, due.tolist())
    cycle = traffic.serve_cycle(spec, 50257, 798041194)
    assert [r.due_s for r in cycle] == list(np.sort(
        traffic.rng_for(798041194, "serve_arrivals").uniform(0.0, period, n)))
