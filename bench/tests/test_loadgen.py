"""The generator: the same seed gives the same traffic, and every seed
the same sizes in another order."""
import collections
import os

from bench import loadgen

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traffic")


def _take(traffic, seed, n, vocab=1000):
    gen = loadgen.requests(traffic, vocab, seed)
    return [next(gen) for _ in range(n)]


def test_same_seed_same_requests():
    t = loadgen.load(os.path.join(TRAFFIC, "decode.json"))
    assert _take(t, 2**33 + 5, 70) == _take(t, 2**33 + 5, 70)


def test_seeds_share_sizes_per_round():
    t = loadgen.load(os.path.join(TRAFFIC, "decode.json"))
    a, b = _take(t, 1, 64), _take(t, 2**40 + 1, 64)
    assert a != b
    for lo in (0, 32):
        for f in (lambda r: len(r.prompt), lambda r: r.max_new_tokens):
            assert (collections.Counter(map(f, a[lo:lo + 32]))
                    == collections.Counter(map(f, b[lo:lo + 32])))
    lens = [len(r.prompt) for r in a]
    outs = [r.max_new_tokens for r in a]
    assert 16 <= min(lens) and max(lens) <= 64
    assert 128 <= min(outs) and max(outs) <= 512
    assert all(r.temperature == 0.0 for r in a)


def test_key_seed_keeps_high_bits():
    assert loadgen.key_seed(7) != loadgen.key_seed(2**33 + 7)
    assert 0 <= loadgen.key_seed(2**40) < 2**31


def test_lognormal_quantiles_clip():
    q = loadgen.quantiles({"dist": "lognormal", "median": 768, "sigma": 0.8,
                           "lo": 256, "hi": 2048}, 64)
    assert q.min() >= 256 and q.max() <= 2048
    assert abs(sorted(q)[32] - 768) < 80


def test_open_loop_arrivals():
    for arrival in ({"process": "poisson", "rate": 4.0},
                    {"process": "gamma", "rate": 4.0, "cv": 2.0}):
        t = {"arrival": arrival, "round": 64}
        a = loadgen.arrival_offsets(t, 3, 100.0)
        assert a == loadgen.arrival_offsets(t, 3, 100.0)
        assert a != loadgen.arrival_offsets(t, 4, 100.0)
        assert all(x < y for x, y in zip(a, a[1:])) and a[-1] < 100.0
        assert 300 < len(a) < 500          # about rate x seconds
