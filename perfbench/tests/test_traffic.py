import collections

import pytest

from perfbench import traffic

MIX = {"loop": "open", "rate_per_s": 50.0,
       "sizes": [16, 64, 256, 1024, 8192, 16384],
       "weights": [0.40, 0.30, 0.15, 0.10, 0.04, 0.01]}
SEEDS = (0, 7, 2**31 + 11, 2**33 + 5)


@pytest.mark.parametrize("seed", SEEDS)
def test_same_seed_same_schedule(seed):
    assert (traffic.open_schedule(MIX, seed, 10)
            == traffic.open_schedule(MIX, seed, 10))


def test_seeds_reorder_the_same_work():
    a = traffic.open_schedule(MIX, SEEDS[0], 10)
    b = traffic.open_schedule(MIX, SEEDS[2], 10)
    assert a != b
    assert len(a) == len(b) == 500
    assert (collections.Counter(n for _, n in a)
            == collections.Counter(n for _, n in b))
    gaps = [sorted(round(y - x, 9) for (x, _), (y, _) in zip(s, s[1:]))
            for s in (a, b)]
    assert abs(sum(gaps[0]) - sum(gaps[1])) < 0.5


def test_open_schedule_fills_the_window_at_the_rate():
    s = traffic.open_schedule(MIX, 3, 20)
    offsets = [t for t, _ in s]
    assert offsets == sorted(offsets)
    assert offsets[0] == 0 and offsets[-1] < 20
    assert len(s) == 1000
    counts = collections.Counter(n for _, n in s)
    assert [counts[n] for n in MIX["sizes"]] == [400, 300, 150, 100, 40, 10]


def test_every_block_of_the_open_loop_has_the_same_work():
    s = traffic.open_schedule(MIX, 2**31 + 9, 20)
    sizes = [n for _, n in s]
    for k in range(0, len(s), traffic.BLOCK):
        block = collections.Counter(sizes[k:k + traffic.BLOCK])
        assert [block[n] for n in MIX["sizes"]] == [40, 30, 15, 10, 4, 1]


def test_class_counts_sum_and_stay_within_one():
    for n in (1, 7, 99, 100, 1234):
        c = traffic.class_counts(MIX["weights"], n)
        assert sum(c) == n
        assert all(abs(x - w * n) < 1 for x, w in zip(c, MIX["weights"]))


@pytest.mark.parametrize("seed", SEEDS)
def test_closed_sizes_repeat_per_seed_in_exact_blocks(seed):
    mix = {"loop": "closed", "clients": 4, "sizes": [16, 64],
           "weights": [0.5, 0.5]}
    take = lambda: [n for n, _ in zip(traffic.closed_sizes(mix, seed),
                                      range(300))]
    a = take()
    assert a == take()
    for k in range(3):
        block = a[k * traffic.BLOCK:(k + 1) * traffic.BLOCK]
        assert block.count(16) == block.count(64) == 50


@pytest.mark.parametrize("config", ["sha3_256-openssl", "tls13-aes128gcm"])
def test_same_seed_same_payloads(config):
    import json
    import os

    from perfbench import spec

    here = os.path.join(spec.HERE, "configs")
    cfg = json.load(open(os.path.join(here, config + ".json")))
    mod = spec.load_module(os.path.join(here, config + ".py"))
    a = mod.Client(cfg, 2**31 + 3, traffic.rng)
    b = mod.Client(cfg, 2**31 + 3, traffic.rng)
    c = mod.Client(cfg, 2**31 + 4, traffic.rng)
    items = [(i, n) for i, n in enumerate([16, 64, 256, 1024] * 3)]
    pa = [a.payload(i, n) for i, n in items]
    assert pa == [b.payload(i, n) for i, n in items]
    assert pa != [c.payload(i, n) for i, n in items]
    assert a.expected(items) == b.expected(items)


def test_mix_files_are_sound():
    import glob
    import json
    import os

    from perfbench import spec

    paths = glob.glob(os.path.join(spec.HERE, "traffic", "*.json"))
    assert paths
    for p in paths:
        mix = json.load(open(p))
        assert mix["loop"] in ("open", "closed")
        assert len(mix["sizes"]) == len(mix["weights"])
        assert abs(sum(mix["weights"]) - 1) < 1e-9
        assert mix["why"]
