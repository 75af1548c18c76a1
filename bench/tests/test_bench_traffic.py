"""The benchmark's traffic generator and window arithmetic (CPU)."""

import math
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import traffic  # noqa: E402

MIX = traffic.Mix.from_dict({
    "mode": "closed_backlog", "n_experts": 3, "zipf_alpha": 1.1,
    "prompt_len": {"median": 128, "sigma": 0.6, "lo": 32, "hi": 512},
    "output_len": {"median": 24, "sigma": 0.5, "lo": 8, "hi": 64},
    "block": 32, "n_requests": 96})


def _key(specs):
    return [(s.uid, s.expert, s.max_new_tokens, tuple(s.prompt.tolist()))
            for s in specs]


def test_same_seed_same_requests():
    a = traffic.generate(MIX, 2**31 + 17, 151936)
    b = traffic.generate(MIX, 2**31 + 17, 151936)
    assert _key(a) == _key(b)
    assert _key(a) != _key(traffic.generate(MIX, 2**31 + 18, 151936))


@pytest.mark.parametrize("seed", [0, 7, 2**33 + 5])
def test_seeds_change_token_ids_only(seed):
    """Every seed serves the same lengths and experts in the same order."""
    a = traffic.generate(MIX, seed, 151936)
    b = traffic.generate(MIX, seed + 1, 151936)
    shape = [(s.expert, len(s.prompt), s.max_new_tokens) for s in a]
    assert shape == [(s.expert, len(s.prompt), s.max_new_tokens) for s in b]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("seed", [0, 7, 2**33 + 5])
def test_every_block_holds_the_same_work(seed):
    """Every block of ``block`` holds the same prompt lengths, output
    lengths and expert counts."""
    specs = traffic.generate(MIX, seed, 1000)
    want = None
    for b in range(0, len(specs), MIX.block):
        blk = specs[b:b + MIX.block]
        got = (sorted(len(s.prompt) for s in blk),
               sorted(s.max_new_tokens for s in blk),
               sorted(s.expert for s in blk))
        want = want or got
        assert got == want
    assert want[0] == sorted(MIX.prompt.quantiles(MIX.block))
    assert all(32 <= n <= 512 for n in want[0])
    assert all(8 <= n <= 64 for n in want[1])
    assert max(int(s.prompt.max()) for s in specs) < 1000


def test_zipf_counts():
    assert traffic.zipf_counts(3, 1.1, 32) == [18, 9, 5]
    assert traffic.zipf_counts(1, 0.0, 32) == [32]
    assert sum(traffic.zipf_counts(4, 1.1, 32)) == 32


def test_lognormal_quantiles_median():
    q = traffic.Lengths(128, 0.6, 32, 512).quantiles(33)
    assert q[16] == 128 and q == sorted(q)


class R:
    def __init__(self, n_out, admit, first, done, status="done", arrival=0.0):
        self.out_tokens = [1] * n_out
        self.t_admit_s, self.t_first_s, self.t_done_s = admit, first, done
        self.status, self.arrival_s = status, arrival


def test_summary_rate_is_all_tokens_over_the_whole_window():
    reqs = [R(10, 0.0, 0.1, 1.0), R(5, 0.5, 0.6, None),     # in flight
            R(0, None, None, None)]                            # never admitted
    s = traffic.summarize(reqs, 2.0)
    assert s["tokens"] == 15 and s["tokens_per_s"] == 7.5
    assert s["attempted"] == 2 and s["finished"] == 1


def test_tails_cover_every_request_and_failures_miss():
    reqs = [R(11, 0.0, 0.0, 1.0 * i) for i in range(1, 10)]
    s = traffic.summarize(reqs, 10.0)
    # request i makes 10 gaps in i seconds: 100*i ms; nearest rank 9 of 9
    assert s["tpot_p90_ms"] == pytest.approx(900.0)
    reqs += [R(0, None, None, None, status="failed")] * 2
    s = traffic.summarize(reqs, 10.0)
    # 11 requests: rank 10 is the first of the two misses
    assert s["failed"] == 2 and math.isinf(s["tpot_p90_ms"])
    assert math.isinf(s["ttft_p90_s"])


def test_percentile_nearest_rank():
    xs = list(np.arange(1, 11, dtype=float))
    assert traffic.percentile(xs, 90) == 9.0
    assert traffic.percentile(xs, 50) == 5.0
    assert traffic.percentile(xs + [math.inf], 95) == math.inf


def test_open_loop_arrivals_share_their_gaps_across_seeds():
    mix = traffic.Mix.from_dict(dict(
        mode="open_loop", rate=2.0, n_experts=3, zipf_alpha=1.1,
        prompt_len={"median": 128, "sigma": 0.6, "lo": 32, "hi": 512},
        output_len={"median": 24, "sigma": 0.5, "lo": 8, "hi": 64},
        block=32, n_requests=64))
    a = traffic.generate(mix, 1, 1000)
    b = traffic.generate(mix, 2, 1000)
    ga = np.diff([s.arrival_s for s in a])
    gb = np.diff([s.arrival_s for s in b])
    assert a[0].arrival_s == 0.0 and np.all(ga > 0)
    assert list(ga) == pytest.approx(list(gb))
    assert not np.allclose(ga[:31], ga[32:63])    # blocks differ in order
    # a block's mean gap is close to 1 / rate
    assert np.mean(ga[:32]) == pytest.approx(0.5, rel=0.15)


def test_due_but_never_admitted_is_a_miss():
    reqs = [R(11, 0.0, 0.1, 1.0)] * 9 + [R(0, None, None, None,
                                           arrival=1.5)] * 2
    s = traffic.summarize(reqs, 2.0)
    assert s["attempted"] == 11 and math.isinf(s["ttft_p90_s"])
