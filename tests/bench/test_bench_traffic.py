"""The traffic generators: deterministic in the seed, the same work for
every seed, and the lengths and rates their mixes state."""
import collections

import numpy as np
import pytest

import tiny  # noqa: F401  (puts bench/ on the path)
import manifest as M
from traffic_common import check_fits, mix_quantile, stratified, rng_for

BIG_SEED = 2 ** 31 + 987654321


def _gen(kind):
    import os
    return M.load_module(os.path.join(M.BENCH_DIR, "generators",
                                      kind + ".py"))


def _mix(name):
    import json
    import os
    with open(os.path.join(M.BENCH_DIR, "traffic", name + ".json")) as f:
        return json.load(f)


def test_mix_quantile_covers_each_component_by_weight():
    mix = [[0.7, 32, 128], [0.3, 129, 192]]
    v = mix_quantile(mix, (np.arange(1000) + 0.5) / 1000)
    assert v.min() == 32 and v.max() == 192
    assert np.sum(v <= 128) == 700
    # uniform within a component: every value of the first one appears
    assert set(range(32, 129)) <= set(v.tolist())


def test_stratified_same_multiset_other_order():
    mix = [[1.0, 512, 1536]]
    a = stratified(mix, 64, rng_for(1))
    b = stratified(mix, 64, rng_for(2))
    assert sorted(a) == sorted(b) and list(a) != list(b)


@pytest.mark.parametrize("name", ["stream-long", "agent-prefix"])
def test_closed_backlog_deterministic_and_in_range(name):
    mix = _mix(name)
    gen = _gen(mix["kind"])
    a = gen.build(mix, BIG_SEED, 50000, 16, 10.0)
    b = gen.build(mix, BIG_SEED, 50000, 16, 10.0)
    c = gen.build(mix, 7, 50000, 16, 10.0)
    assert a.closed and len(a.backlog) == mix["backlog"]
    for x, y in zip(a.backlog, b.backlog):
        assert np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
    pre = int(mix.get("prefix_len", 0))
    # every block of `clients` consecutive requests holds the same
    # requests' sizes (prompt with its output) whatever the seed, in
    # another order
    k = mix["clients"]
    first = sorted((len(r.prompt), r.max_new) for r in a.backlog[:k])
    for i in range(0, len(a.backlog), k):
        for x in (a, c):
            assert sorted((len(r.prompt), r.max_new)
                          for r in x.backlog[i:i + k]) == first
    assert [len(r.prompt) for r in a.backlog[:k]] != \
        [len(r.prompt) for r in c.backlog[:k]]
    (_, lo, hi), = mix["prompt_mix"]
    (_, olo, ohi), = mix["output_mix"]
    for r in a.backlog:
        assert pre + lo <= len(r.prompt) <= pre + hi
        assert olo <= r.max_new <= ohi
        assert r.prompt.min() >= 1 and r.prompt.max() < 50000
    first = a.initial()
    assert [r.client for r in first] == list(range(mix["clients"]))
    assert a.next_request(3).client == 3 and a.due(1e9) == []
    if pre:
        uses = collections.Counter(
            next(i for i, p in enumerate(a.prefixes)
                 if np.array_equal(r.prompt[:pre], p)) for r in a.backlog)
        assert len(a.prefixes) == mix["n_prefixes"]
        assert uses[0] == uses[1] == mix["backlog"] // 2
    with pytest.raises(ValueError):
        gen.build(dict(mix, backlog=mix["backlog"] + 1), 1, 50000, 16, 10.0)


def test_open_poisson_rate_count_and_determinism():
    mix = dict(_mix("short-poisson"), rate=150.0)
    gen = _gen("open_poisson")
    secs = 10.0
    a = gen.build(mix, BIG_SEED, 50257, 16, secs)
    b = gen.build(mix, BIG_SEED, 50257, 16, secs)
    c = gen.build(mix, 11, 50257, 16, secs)
    n = round(150.0 * secs)
    assert not a.closed and len(a.requests) == n
    due = np.array([r.due for r in a.requests])
    assert np.all(np.diff(due) > 0) and 0 <= due[0] and due[-1] < secs
    assert n / secs == pytest.approx(150.0)
    gaps = np.diff(due)
    # exponential inter-arrivals: coefficient of variation near 1
    assert 0.8 < gaps.std() / gaps.mean() < 1.2
    assert [r.due for r in b.requests] == list(due)
    assert sorted(np.diff([r.due for r in c.requests]).round(9)) != [] and \
        sorted(len(r.prompt) for r in c.requests) == \
        sorted(len(r.prompt) for r in a.requests)
    short = sum(len(r.prompt) <= 128 for r in a.requests)
    assert short == round(0.7 * n)
    for r in a.requests:
        assert 32 <= len(r.prompt) <= 192 and 16 <= r.max_new <= 48
    # handed out once each, in due order
    got = a.due(5.0) + a.due(5.0) + a.due(secs)
    assert [r.index for r in got] == list(range(n)) and a.next_due() is None


def test_mix_longer_than_max_len_is_refused():
    mix = dict(_mix("short-poisson"), max_len=200)
    with pytest.raises(ValueError):
        check_fits(mix, 16)
    for name in ("stream-long", "short-poisson", "agent-prefix"):
        check_fits(_mix(name), 16)
