"""The one traffic generator. A mix is a DATA file,
``benchmarks/traffic/<mix>.json``; this module turns its parameters and
a seed into requests (serving) or batches of packed documents
(training). A new mix is a new file, not new code.

Keys of a mix file (``kind`` decides which apply):

``kind: "requests"``
    ``classes``: list of ``{"weight", "prompt_len", "output_len"}`` —
    or, for one class, ``prompt_len`` and ``output_len`` at top level.
    A length is a distribution: ``{"dist": "lognormal", "median",
    "sigma", "low", "high"}`` (clipped to [low, high]), ``{"dist":
    "uniform", "low", "high"}`` or ``{"dist": "fixed", "value"}``.
    ``shared_prefix``: optional ``{"len", "pools"}`` — every prompt
    opens with one of ``pools`` fixed token runs of ``len`` tokens, so
    the prefix cache has something to hit; without it tokens are
    uniform over the vocabulary and no two prompts share a block.
    ``arrivals``: ``{"kind": "backlog"}`` (no due times: the driver
    keeps the queue full) or ``{"kind": "gamma", "rate_rps", "cv"}``
    (``cv`` 1 is a Poisson process, above 1 is bursty) with an optional
    ``lead_in_s``.
    ``stratify``: N. Draws come in blocks of N whose quantile ranks are
    the N equal strata of the distribution, shuffled by the seed. Every
    seed then offers the SAME multiset of lengths and inter-arrival
    gaps in every block, in another order: seeds change the order of
    the work, not its amount, which is what keeps two runs comparable.

``kind: "documents"``
    ``seq_len``, ``doc_len`` (a distribution), ``rows_per_chunk``:
    documents of random tokens are packed ``rows_per_chunk`` rows at a
    time by the program's own ``data.pack_documents`` (EOS between
    documents, no padding) and served as fresh ``[batch, seq_len]``
    batches by ``PackedLMDataset.batches``, for ever.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

_NORMAL = statistics.NormalDist()


def _rng(seed: int, tag: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), tag])


def quantile(dist: Dict, u: float) -> float:
    """The ``u``-quantile (0 < u < 1) of a distribution entry."""
    kind = dist["dist"]
    if kind == "fixed":
        return float(dist["value"])
    if kind == "uniform":
        x = dist["low"] + u * (dist["high"] + 1 - dist["low"])
        return float(min(math.floor(x), dist["high"]))
    if kind == "lognormal":
        x = math.exp(math.log(dist["median"])
                     + dist["sigma"] * _NORMAL.inv_cdf(u))
        return float(min(max(x, dist["low"]), dist["high"]))
    if kind == "gamma":          # mean 1, coefficient of variation cv
        return _gamma_quantile(dist["cv"], u)
    raise ValueError(f"unknown distribution {kind!r} in a traffic file")


def _gamma_quantile(cv: float, u: float) -> float:
    """Quantile of a gamma distribution with mean 1 and the given
    coefficient of variation (shape 1/cv^2). cv = 1 is the exponential,
    in closed form; otherwise bisection on the regularised incomplete
    gamma function's series (no scipy here)."""
    if abs(cv - 1.0) < 1e-12:
        return -math.log1p(-u)
    k = 1.0 / (cv * cv)

    def cdf(x: float) -> float:
        if x <= 0:
            return 0.0
        # lower regularised gamma P(k, k*x) by its power series
        z = k * x
        term = total = 1.0 / k
        n = 1
        while abs(term) > 1e-15 * abs(total) and n < 10000:
            term *= z / (k + n)
            total += term
            n += 1
        return min(1.0, total * math.exp(-z + k * math.log(z)
                                         - math.lgamma(k)))

    lo, hi = 0.0, 1.0
    while cdf(hi) < u:
        hi *= 2
    for _ in range(80):
        mid = (lo + hi) / 2
        lo, hi = (mid, hi) if cdf(mid) < u else (lo, mid)
    return (lo + hi) / 2


def draws(dist: Dict, rng: np.random.Generator,
          stratify: int) -> Iterator[float]:
    """Endless draws from ``dist``, in shuffled blocks of ``stratify``
    equal strata (``stratify`` 0 or 1: plain independent draws)."""
    while True:
        if stratify and stratify > 1:
            ranks = (rng.permutation(stratify) + 0.5) / stratify
        else:
            ranks = [float(rng.uniform(1e-9, 1 - 1e-9))]
        for u in ranks:
            yield quantile(dist, float(u))


@dataclasses.dataclass
class Req:
    index: int
    due_s: Optional[float]      # from the stream's start; None = backlog
    prompt: np.ndarray          # int32 tokens
    max_new: int


def _classes(spec: Dict) -> List[Dict]:
    if "classes" in spec:
        return list(spec["classes"])
    return [{"weight": 1.0, "prompt_len": spec["prompt_len"],
             "output_len": spec["output_len"]}]


def requests(spec: Dict, vocab_size: int, seed: int) -> Iterator[Req]:
    """Endless requests of a ``kind: "requests"`` mix."""
    if spec.get("kind") != "requests":
        raise ValueError(f"traffic kind {spec.get('kind')!r} is not "
                         f"'requests'")
    n_strata = int(spec.get("stratify", 0))
    classes = _classes(spec)
    weights = np.asarray([c.get("weight", 1.0) for c in classes], float)
    weights /= weights.sum()
    lens = [(draws(c["prompt_len"], _rng(seed, 10 + 2 * i), n_strata),
             draws(c["output_len"], _rng(seed, 11 + 2 * i), n_strata))
            for i, c in enumerate(classes)]
    pick = _rng(seed, 1)
    tok = _rng(seed, 2)
    arr = spec.get("arrivals", {"kind": "backlog"})
    if arr["kind"] == "backlog":
        gap_draws = None
    elif arr["kind"] == "gamma":
        gap_draws = draws({"dist": "gamma", "cv": float(arr.get("cv", 1))},
                          _rng(seed, 3), n_strata)
    else:
        raise ValueError(f"unknown arrivals kind {arr['kind']!r}")
    shared = spec.get("shared_prefix")
    prefixes = None
    if shared:
        prefixes = _rng(seed, 4).integers(
            0, vocab_size, (int(shared["pools"]), int(shared["len"])))
    due = 0.0
    for index in range(1 << 62):
        c = int(pick.choice(len(classes), p=weights))
        n_prompt = int(next(lens[c][0]))
        n_out = int(next(lens[c][1]))
        prompt = tok.integers(0, vocab_size, (n_prompt,)).astype(np.int32)
        if prefixes is not None:
            pre = prefixes[int(pick.integers(len(prefixes)))]
            m = min(len(pre), n_prompt)
            prompt[:m] = pre[:m]
        if gap_draws is not None:
            due += next(gap_draws) / float(arr["rate_rps"])
        yield Req(index, None if gap_draws is None else due, prompt, n_out)


def document_batches(spec: Dict, vocab_size: int, batch: int, seed: int
                     ) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
    """Endless ``(input_ids, labels)`` batches of a ``kind:
    "documents"`` mix. The packing and batching are the program's
    (``quintnet_tpu.data``): they are the input pipeline under test."""
    from quintnet_tpu.data import PackedLMDataset, pack_documents

    if spec.get("kind") != "documents":
        raise ValueError(f"traffic kind {spec.get('kind')!r} is not "
                         f"'documents'")
    seq = int(spec["seq_len"])
    rows = max(int(spec.get("rows_per_chunk", 256)), batch)
    eos = vocab_size - 1
    lens = draws(spec["doc_len"], _rng(seed, 20),
                 int(spec.get("stratify", 0)))
    tok = _rng(seed, 21)
    for chunk in range(1 << 62):
        docs, have = [], 0
        while have < rows * seq:
            n = int(next(lens))
            docs.append(tok.integers(0, eos, (n,)))
            have += n + 1
        ds = PackedLMDataset(pack_documents(docs, seq, eos_id=eos))
        yield from ds.batches(batch, seed=(seed + chunk) & 0x7FFFFFFF)
