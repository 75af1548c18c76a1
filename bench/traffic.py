"""Seeded traffic for the benchmark's cells, read from ``mixes/<name>.json``.

Adapted from ``benchmarks/traffic.py`` (Zipf expert popularity, seeded
prompts), with three changes:

* lengths are drawn from a clipped lognormal, and every block of
  ``block`` consecutive requests holds the same multiset of prompt
  lengths, output lengths and experts (the distribution's quantiles and
  the Zipf counts), in an order drawn from the fixed ``ORDER_SEED``.
  Every run seed then serves the same work in the same order: the seed
  draws the token ids alone, so it cannot move which requests form a
  latency tail;
* token ids come from the configuration's vocabulary;
* ``mode: "closed_backlog"`` queues every request at time 0, so the
  engine never lacks work (offline evaluation of a task suite);
  ``mode: "open_loop"`` sends them at ``rate`` requests a second, with
  the gaps of each block the exponential's quantiles in that order
  (Poisson arrivals that every seed shares).

``summarize`` counts a request that failed or never produced a token as
a miss (an infinite latency), never as a request left out.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

MODES = ("closed_backlog", "open_loop")
ORDER_SEED = 0x0D3E


@dataclasses.dataclass(frozen=True)
class Lengths:
    median: float
    sigma: float
    lo: int
    hi: int

    def quantiles(self, n: int) -> list[int]:
        """The ``n`` mid-quantiles of the clipped lognormal."""
        nd = NormalDist()
        out = []
        for i in range(n):
            z = nd.inv_cdf((i + 0.5) / n)
            v = int(round(self.median * math.exp(self.sigma * z)))
            out.append(min(max(v, self.lo), self.hi))
        return out


@dataclasses.dataclass(frozen=True)
class Mix:
    mode: str
    n_experts: int
    zipf_alpha: float
    prompt: Lengths
    output: Lengths
    block: int
    n_requests: int
    rate: float = 0.0

    @classmethod
    def from_dict(cls, d: dict) -> "Mix":
        if d["mode"] not in MODES:
            raise ValueError(f"unknown traffic mode {d['mode']!r}; "
                             f"known: {MODES}")
        return cls(mode=d["mode"], n_experts=int(d["n_experts"]),
                   zipf_alpha=float(d["zipf_alpha"]),
                   prompt=Lengths(**d["prompt_len"]),
                   output=Lengths(**d["output_len"]),
                   block=int(d["block"]), n_requests=int(d["n_requests"]),
                   rate=float(d.get("rate", 0.0)))


def zipf_weights(n: int, alpha: float) -> np.ndarray:
    """P(expert k) proportional to (k+1)^-alpha."""
    w = np.arange(1, n + 1, dtype=np.float64) ** (-alpha)
    return w / w.sum()


def zipf_counts(n: int, alpha: float, total: int) -> list[int]:
    """``total`` requests split over ``n`` experts by largest remainder of
    the Zipf weights; every expert gets at least one."""
    if total < n:
        raise ValueError(f"a block of {total} cannot hold {n} experts")
    exact = zipf_weights(n, alpha) * total
    counts = np.maximum(np.floor(exact).astype(int), 1)
    while counts.sum() < total:
        counts[int(np.argmax(exact - counts))] += 1
    while counts.sum() > total:
        counts[int(np.argmax(counts))] -= 1
    return [int(c) for c in counts]


@dataclasses.dataclass
class Spec:
    """One request as generated: the engine's Request is built from it."""
    uid: int
    expert: str
    prompt: np.ndarray        # int32 token ids
    max_new_tokens: int
    arrival_s: float = 0.0


def generate(mix: Mix, seed: int, vocab: int,
             n_requests: int | None = None) -> list[Spec]:
    """The seeded request list.  Equal (mix, seed, vocab) give equal lists;
    seeds differ in token ids only."""
    rng = np.random.default_rng(seed)
    order = np.random.default_rng(ORDER_SEED)
    n = mix.n_requests if n_requests is None else n_requests
    plens = mix.prompt.quantiles(mix.block)
    olens = mix.output.quantiles(mix.block)
    experts = [e for e, c in enumerate(zipf_counts(mix.n_experts,
                                                   mix.zipf_alpha,
                                                   mix.block))
               for _ in range(c)]
    gaps = ([-math.log(1.0 - (i + 0.5) / mix.block) / mix.rate
             for i in range(mix.block)] if mix.mode == "open_loop" else
            [0.0] * mix.block)
    out: list[Spec] = []
    t = 0.0
    while len(out) < n:
        p = order.permutation(plens)
        o = order.permutation(olens)
        e = order.permutation(experts)
        g = order.permutation(gaps)
        for i in range(mix.block):
            if len(out) == n:
                break
            out.append(Spec(
                uid=len(out), expert=f"expert{int(e[i])}",
                prompt=rng.integers(2, vocab, size=int(p[i]),
                                    dtype=np.int32),
                max_new_tokens=int(o[i]), arrival_s=t))
            t += float(g[i])
    return out


def warmup(mix: Mix, seed: int, vocab: int, bucket: int) -> list[Spec]:
    """Requests that mint every program shape the cell's window uses, at
    little cost: one block (the first wave's batch and its prefill
    groups), then one request for each prompt-length bucket of the block
    (the one-row prefills of refilled slots), each asking for two tokens
    (so one decode chunk runs), all queued at once."""
    block = generate(mix, seed, vocab, mix.block)
    first: dict = {}
    for s in block:
        first.setdefault(-(-len(s.prompt) // bucket), s)
    out = block + [dataclasses.replace(s, uid=len(block) + i)
                   for i, s in enumerate(first.values())]
    for s in out:
        s.max_new_tokens = 2
        s.arrival_s = 0.0
    return out


def percentile(xs: list[float], q: float) -> float:
    """Nearest-rank percentile; ``inf`` entries (misses) sort last."""
    if not xs:
        raise ValueError("percentile of no values")
    s = sorted(xs)
    k = max(1, math.ceil(q / 100.0 * len(s)))
    return s[k - 1]


def tpot_s(r) -> float | None:
    """Seconds per output token after the first, for a finished request
    with two or more tokens; None otherwise."""
    if r.t_done_s is None or r.t_first_s is None or len(r.out_tokens) < 2:
        return None
    return (r.t_done_s - r.t_first_s) / (len(r.out_tokens) - 1)


def summarize(requests, window_s: float) -> dict:
    """Window statistics over the requests the window attempted.

    ``window_s`` is the measured time from the window's start to its
    close.  tokens/s is every token the engine emitted in that time over
    that time.  A request counts as attempted once admitted or, in an
    open loop, once due; one that failed or was due and never admitted
    counts as a miss (infinite latency) in the tails.
    """
    attempted = [r for r in requests if r.t_admit_s is not None
                 or r.status == "failed" or 0.0 < r.arrival_s < window_s]
    failed = [r for r in attempted if r.status == "failed"]
    finished = [r for r in attempted if r.status != "failed"
                and r.t_done_s is not None]
    tpot = [tpot_s(r) for r in finished]
    tpot = [t for t in tpot if t is not None] + [math.inf] * len(failed)
    waits = [(r.t_admit_s - r.arrival_s) if r.t_admit_s is not None
             else math.inf for r in attempted]
    ttft = [(r.t_first_s - r.arrival_s) if r.t_first_s is not None
            else math.inf for r in attempted]
    tokens = sum(len(r.out_tokens) for r in requests)
    return {
        "attempted": len(attempted), "failed": len(failed),
        "finished": len(finished), "tokens": tokens,
        "window_s": window_s,
        "tokens_per_s": tokens / window_s,
        "tpot_p90_ms": 1e3 * percentile(tpot, 90) if tpot else None,
        "ttft_p50_s": percentile(ttft, 50) if ttft else None,
        "ttft_p90_s": percentile(ttft, 90) if ttft else None,
        "queue_wait_p90_s": percentile(waits, 90) if waits else None,
    }
