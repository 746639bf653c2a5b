"""The general traffic generator: seeded, same amount of work per seed,
lengths inside the mix's stated bounds."""
import json
import os

import numpy as np
import pytest

from harness import traffic

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "traffic")


def mix(name):
    with open(os.path.join(TRAFFIC, name + ".json")) as f:
        return json.load(f)


def flat(streams):
    return [(s.sid, s.arrival_s, j, t.user.tolist(), t.max_new, t.think_s)
            for s in streams for j, t in enumerate(s.turns)]


@pytest.mark.parametrize("name", ["sessions", "unshared", "reasoning"])
def test_same_seed_same_streams(name):
    a = traffic.generate(mix(name), 2 ** 40 + 7, 30, 1000)
    b = traffic.generate(mix(name), 2 ** 40 + 7, 30, 1000)
    c = traffic.generate(mix(name), 5, 30, 1000)
    assert flat(a) == flat(b)
    assert flat(a) != flat(c)


@pytest.mark.parametrize("name", ["sessions", "unshared", "reasoning"])
def test_every_seed_gets_the_same_sizes(name):
    """Every seed sends the same streams - arrivals, turns, prompt and
    answer lengths, think times, which streams share a system prompt -
    whatever the seed; only the token ids move, and in a closed loop which
    client runs which stream."""
    def sizes(seed):
        ss = traffic.generate(mix(name), seed, 30, 1000)
        first = {}
        for s in sorted(ss, key=lambda s: (s.arrival_s, s.turns[0].max_new)):
            first.setdefault(s.system.tobytes(), len(first))
        return sorted((s.arrival_s, first[s.system.tobytes()],
                       [(len(t.user), t.max_new, t.think_s) for t in s.turns])
                      for s in ss)

    assert sizes(1) == sizes(2 ** 33 + 1) == sizes(4300000043)


def test_open_loop_gaps_are_a_seed_free_multiset():
    """Every seed's streams arrive at the same times, in the same order."""
    m = mix("unshared")
    pool = np.cumsum(traffic._gaps(m["arrivals"], 46))
    for seed in (1, 2, 2 ** 40 + 3):
        arr = [s.arrival_s for s in traffic.generate(m, seed, 30, 1000)]
        assert len(arr) == 46 and arr[0] == 0
        assert np.allclose(arr, pool - pool[0])


def test_lengths_follow_the_stated_distribution():
    m = mix("unshared")
    ss = traffic.generate(m, 9, 400, 1000)
    p = np.array([len(t.user) for s in ss for t in s.turns])
    o = np.array([t.max_new for s in ss for t in s.turns])
    assert p.min() >= m["prompt"]["min"] and p.max() <= m["prompt"]["max"]
    assert o.min() >= m["output"]["min"] and o.max() <= m["output"]["max"]
    assert abs(np.median(p) - m["prompt"]["median"]) < 0.15 * m["prompt"]["median"]
    assert abs(np.median(o) - m["output"]["median"]) < 0.15 * m["output"]["median"]
    # Bursty arrivals: the gaps' coefficient of variation is near the mix's.
    arr = np.sort([s.arrival_s for s in ss])
    g = np.diff(arr)
    assert abs(g.std() / g.mean() - m["arrivals"]["cv"]) < 0.5


def test_sessions_stay_under_max_prompt_and_share_system_prompts():
    m = mix("sessions")
    ss = traffic.generate(m, 3, 60, 1000)
    systems = {s.system.tobytes() for s in ss}
    assert len(systems) == m["system"]["count"]
    for s in ss:
        assert m["turns"]["min"] >= 1 and len(s.turns) <= m["turns"]["max"]
        answers = [[0] * t.max_new for t in s.turns]
        for j in range(len(s.turns)):
            p = s.prompt(j, answers)
            assert len(p) <= m["max_prompt"]
            assert (p[:m["system"]["tokens"]] == s.system).all()
            if j:   # history: the previous prompt, its answer, a new turn
                prev = s.prompt(j - 1, answers)
                assert (p[:len(prev)] == prev).all()


def test_closed_loop_clients_all_start_at_once():
    m = mix("reasoning")
    ss = traffic.generate(m, 4, 30, 1000)
    assert len(ss) == m["arrivals"]["clients"]
    assert all(s.arrival_s == 0 for s in ss)
    assert all(len(s.turns) == m["turns"]["min"] for s in ss)
