"""One general generator per traffic kind, reading a mix's parameters.

Every seed gets the same set of sizes and arrival gaps, in another order:
lengths and gaps are the stratified quantiles of their distributions (a
fixed multiset of ``cycle`` entries), and the seed only permutes them and
draws the token ids.  So two seeds do the same work, and a spread between
them is the system's, not the dice's.

Copied in spirit from ``rocket_tpu/serve/loadgen.py`` (one seeded
``default_rng``, arrivals as a cumulative sum of exponential gaps), with
the cell's sizes and a lateness report; see PERF.md, Open questions.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List, Optional

import numpy as np

_NORMAL = NormalDist()


@dataclasses.dataclass
class Req:
    """One request: ``due_s`` is seconds after the window opens (negative or
    None for the rows already in service when it opens)."""

    rid: int
    prompt: np.ndarray
    max_new: int
    due_s: Optional[float]
    warm: bool = False


def lognormal_quantiles(median: float, sigma: float, n: int,
                        lo: Optional[int] = None,
                        hi: Optional[int] = None) -> np.ndarray:
    """``n`` whole numbers at the quantiles (i + 0.5) / n of a log-normal,
    clipped to ``[lo, hi]``."""
    qs = (np.arange(n) + 0.5) / n
    vals = np.array([math.exp(math.log(median) + sigma * _NORMAL.inv_cdf(q))
                     for q in qs])
    vals = np.rint(vals)
    if lo is not None or hi is not None:
        vals = np.clip(vals, lo, hi)
    return vals.astype(np.int64)


def ladder_counts(ladder: List[int], median: float, sigma: float,
                  n: int) -> List[int]:
    """How many of ``n`` prompts fall on each step of ``ladder``: the mass a
    log-normal puts between the geometric midpoints of neighbouring steps,
    rounded by largest remainder so the counts sum to ``n``."""
    edges = [math.sqrt(a * b) for a, b in zip(ladder, ladder[1:])]
    cdf = [_NORMAL.cdf((math.log(e) - math.log(median)) / sigma)
           for e in edges]
    mass = np.diff([0.0] + cdf + [1.0])
    raw = mass * n
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts))[: n - counts.sum()]:
        counts[i] += 1
    return [int(c) for c in counts]


def exponential_gaps(rate_per_s: float, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps at the quantiles of Exp(rate), scaled so
    their mean is exactly ``1 / rate``."""
    qs = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-qs)
    return gaps / gaps.mean() / rate_per_s


def request_sizes(mix: Dict) -> Dict[str, np.ndarray]:
    """The fixed multiset of one cycle: prompt lengths on the ladder and
    output lengths, ``cycle`` of each."""
    n = int(mix["cycle"])
    p = mix["prompt_lognormal"]
    counts = ladder_counts(mix["prompt_ladder"], p["median"], p["sigma"], n)
    prompts = np.repeat(np.asarray(mix["prompt_ladder"]), counts)
    o = mix["output_lognormal"]
    outputs = lognormal_quantiles(o["median"], o["sigma"], n,
                                  o.get("min"), o.get("max"))
    return {"prompts": prompts, "outputs": outputs}


def serving_requests(mix: Dict, vocab: int, seed: int,
                     count: int) -> List[Req]:
    """``count`` requests for a ``closed`` or ``open`` mix.

    The first ``initial_in_service`` stand for the rows a steady server
    already holds when the window opens: their remaining output is a
    stratified share of the full length, so rows finish spread over the
    window instead of all at once.  The rest arrive in the window: at
    ``due_s`` for an open loop, as soon as a row frees for a closed one
    (``due_s`` None)."""
    rng = np.random.default_rng(int(seed))
    sizes = request_sizes(mix)
    n = int(mix["cycle"])
    prompts: List[int] = []
    outputs: List[int] = []
    while len(prompts) < count:
        prompts += list(sizes["prompts"][rng.permutation(n)])
        outputs += list(sizes["outputs"][rng.permutation(n)])
    limit = int(mix["max_total"])
    n_warm = int(mix.get("initial_in_service", 0))
    shares = (rng.permutation(n_warm) + 0.5) / max(1, n_warm)
    dues: List[Optional[float]] = [None] * count
    if mix["kind"] == "open":
        gaps: List[float] = []
        base = exponential_gaps(float(mix["rate_per_s"]), n)
        while len(gaps) < count:
            gaps += list(base[rng.permutation(n)])
        arrivals = np.cumsum(gaps[: count - n_warm])
        dues = [None] * n_warm + [float(t) for t in arrivals]
    out: List[Req] = []
    floor = int(mix["output_lognormal"].get("min", 1))
    for i in range(count):
        p_len, o_len = int(prompts[i]), int(outputs[i])
        o_len = min(o_len, limit - p_len)
        warm = i < n_warm
        if warm:
            o_len = max(floor, int(math.ceil(o_len * shares[i])))
        tokens = rng.integers(0, vocab, size=p_len).astype(np.int32)
        out.append(Req(rid=i, prompt=tokens, max_new=o_len, due_s=dues[i],
                       warm=warm))
    return out


def markov_tokens(n_docs: int, seq: int, vocab: int, seed: int,
                  branching: int = 4) -> np.ndarray:
    """``[n_docs, seq]`` int32 token rows, each a walk on a seeded Markov
    chain where every token has ``branching`` successors: learnable, and no
    two rows alike."""
    rng = np.random.default_rng(int(seed))
    mult = int(rng.integers(3, 1 << 12)) * 2 + 1
    shift = int(rng.integers(0, vocab))
    out = np.empty((n_docs, seq), np.int64)
    out[:, 0] = rng.integers(0, vocab, size=n_docs)
    steps = rng.integers(0, branching, size=(n_docs, seq))
    for t in range(1, seq):
        out[:, t] = (out[:, t - 1] * mult + shift + steps[:, t]) % vocab
    return out.astype(np.int32)


def lateness_ms(submitted_s: List[float], due_s: List[float]) -> List[float]:
    """How late the generator ran for each request, in milliseconds."""
    return [max(0.0, (s - d)) * 1e3 for s, d in zip(submitted_s, due_s)]
