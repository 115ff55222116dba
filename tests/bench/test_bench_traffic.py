"""The traffic generator repeats per seed, offers every seed the same work,
and samples the checked requests from all of a client's requests."""
import collections

import numpy as np

from bench.lib import traffic
from bench_fixtures import CLOSED

BIG_SEED = 2 ** 31 + 12345
SPEC = dict(loop="closed", clients=3, sizes={"kind": "fixed", "value": 4},
            classes={"relaxed": {"share": 1.0, "budget_ms": 1000}},
            payload_pool=16, check_requests=6)


def draw(seed, n=30, spec=SPEC):
    c = traffic.ClosedClients(spec, seed)
    return c, [c.next(i % spec["clients"]) for i in range(n)]


def test_closed_clients_repeat_per_seed_and_differ_across_seeds():
    assert draw(BIG_SEED)[1] == draw(BIG_SEED)[1]
    assert draw(1)[1] != draw(2)[1]


def test_every_seed_offers_the_same_sizes_and_class():
    for seed in (1, 2, BIG_SEED):
        arrivals = draw(seed)[1]
        assert {a.size for a in arrivals} == {4}
        assert {(a.klass, a.budget_s) for a in arrivals} == {("relaxed", 1.0)}


def test_checked_requests_are_a_reservoir_per_client():
    clients, arrivals = draw(5, n=300)
    final = [a for a in arrivals if clients.checked(a.client, a.index)]
    assert collections.Counter(a.client for a in final) == {0: 2, 1: 2, 2: 2}
    assert all(a.check for a in final)        # each entered when it was sent


def test_checked_requests_reach_the_end_of_a_long_window():
    # Over many seeds the sample of 100 requests per client is uniform: its
    # mean index is near the middle, not at the start.
    means = []
    for seed in range(40):
        clients, arrivals = draw(seed, n=300)
        means += [a.index for a in arrivals
                  if clients.checked(a.client, a.index)]
    assert 35 < np.mean(means) < 65


def test_payload_pool_repeats_per_seed():
    a = traffic.payload_pool(CLOSED, (4, 4, 3), BIG_SEED)
    assert a.dtype == np.uint8 and a.shape == (64, 4, 4, 3)
    assert np.array_equal(a, traffic.payload_pool(CLOSED, (4, 4, 3), BIG_SEED))
    assert not np.array_equal(a, traffic.payload_pool(CLOSED, (4, 4, 3), 3))
    assert list(traffic.payload_indices(62, 1, 3, 64)) == [63, 0, 1]
