"""The one traffic generator: corpus, queries, candidate lists and the
open-loop arrival schedule of a cell, all from ``--seed`` and a traffic
file's parameters (``bench/traffic/<name>.json``).

Every seed gets the same *set* of sizes in another order, so seeds change
which documents and tokens a run touches, not how much work it does:

* document lengths are the log-normal's quantiles at ``(i + 0.5) / n``,
  clipped, then shuffled over the corpus;
* query lengths cycle through ``[len_min, len_max]``, then are shuffled;
* the ``N = round(rate * seconds)`` arrival gaps are the exponential
  distribution's quantiles (a Poisson process's gaps), shuffled and scaled
  so that exactly ``N`` requests fall due in ``[0, seconds)``, the first at
  0 and the last gap running to the window's close.

Token ids are zipf over the vocabulary (ids below ``N_SPECIAL`` are the
BERT special tokens).  Candidates are drawn without replacement with zipf
popularity over a seeded permutation of the corpus; with ``rotate_fraction``
the permutation rotates by that share of the corpus every
``rotate_period_s`` seconds of the schedule, so the popular head moves.
"""
from __future__ import annotations

import dataclasses
import math
import zlib

import numpy as np

PAD, CLS, SEP = 0, 1, 2
N_SPECIAL = 4


@dataclasses.dataclass
class Request:
    index: int
    due_s: float                 # seconds after the window opens
    query: np.ndarray            # raw query token ids (no [CLS]/[SEP])
    doc_ids: np.ndarray          # [C] candidate doc ids


@dataclasses.dataclass
class Traffic:
    docs: list                   # raw doc token ids, one array per doc
    requests: list               # the window's requests, in due order
    warm: list                   # set-up requests (not timed)


def _rng(seed: int, stream: str) -> np.random.Generator:
    """Independent generator per stream: changing one part of the traffic
    file leaves the other streams' draws alone."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFFFFFF,
                                  zlib.crc32(stream.encode())])


def _ids(rng, n: int, vocab: int, zipf: float) -> np.ndarray:
    return (N_SPECIAL + (rng.zipf(zipf, n) - 1) % (vocab - N_SPECIAL)) \
        .astype(np.int32)


def _normal_ppf(p: np.ndarray) -> np.ndarray:
    """Inverse standard normal CDF (Acklam's rational approximation,
    relative error below 1.2e-9): numpy has no erfinv."""
    a = [-3.969683028665376e+01, 2.209460984245205e+02,
         -2.759285104469687e+02, 1.383577518672690e+02,
         -3.066479806614716e+01, 2.506628277459239e+00]
    b = [-5.447609879822406e+01, 1.615858368580409e+02,
         -1.556989798598866e+02, 6.680131188771972e+01,
         -1.328068155288572e+01]
    c = [-7.784894002430293e-03, -3.223964580411365e-01,
         -2.400758277161838e+00, -2.549732539343734e+00,
         4.374664141464968e+00, 2.938163982698783e+00]
    d = [7.784695709041462e-03, 3.224671290700398e-01,
         2.445134137142996e+00, 3.754408661907416e+00]
    p = np.asarray(p, np.float64)
    out = np.empty_like(p)
    lo, hi = p < 0.02425, p > 1 - 0.02425
    mid = ~(lo | hi)
    q = p[mid] - 0.5
    r = q * q
    out[mid] = ((((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4]) * r
                 + a[5]) * q / (((((b[0] * r + b[1]) * r + b[2]) * r + b[3])
                                 * r + b[4]) * r + 1))
    for sel, sign in ((lo, 1.0), (hi, -1.0)):
        q = np.sqrt(-2 * np.log(np.where(sign > 0, p[sel], 1 - p[sel])))
        out[sel] = sign * ((((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q
                             + c[4]) * q + c[5])
                           / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q
                              + 1))
    return out


def doc_lengths(corpus: dict, seed: int) -> np.ndarray:
    """Raw token count of every document (before the trailing [SEP])."""
    n = int(corpus["n_docs"])
    p = (np.arange(n) + 0.5) / n
    lens = np.exp(math.log(corpus["doc_len_median"])
                  + corpus["doc_len_sigma"] * _normal_ppf(p))
    lens = np.clip(np.rint(lens), corpus["doc_len_min"],
                   corpus["doc_len_max"]).astype(np.int64)
    return _rng(seed, "doc_lengths").permutation(lens)


def arrival_times(rate: float, seconds: float, seed: int) -> np.ndarray:
    """``round(rate * seconds)`` due times in ``[0, seconds)``, the first
    at 0: shuffled exponential-quantile gaps scaled so that they and the
    gap from the last arrival to the window's close sum to ``seconds``."""
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = _rng(seed, "arrivals").permutation(gaps) * (seconds / gaps.sum())
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


class Popularity:
    """Zipf popularity over a seeded permutation of the corpus, rotated by
    ``rotate_fraction`` of the corpus every ``rotate_period_s``."""

    def __init__(self, n_docs: int, cand: dict, seed: int):
        self.n = n_docs
        self.perm = _rng(seed, "popularity").permutation(n_docs)
        w = 1.0 / np.arange(1, n_docs + 1) ** float(cand["popularity_zipf"])
        self.p = w / w.sum()
        self.shift = int(round(cand.get("rotate_fraction", 0.0) * n_docs))
        self.period = float(cand.get("rotate_period_s", 0.0))

    def docs_by_rank(self, t: float) -> np.ndarray:
        k = int(t // self.period) if self.period > 0 and self.shift else 0
        return np.roll(self.perm, -k * self.shift)

    def draw(self, rng, k: int, t: float) -> np.ndarray:
        ranks = rng.choice(self.n, size=k, replace=False, p=self.p)
        return self.docs_by_rank(t)[ranks].astype(np.int64)


def generate(spec: dict, vocab: int, seed: int, seconds: float) -> Traffic:
    """The whole traffic of one run of a cell from its traffic file."""
    corpus, qs, cand = spec["corpus"], spec["queries"], spec["candidates"]
    zipf = float(corpus.get("token_zipf", 1.3))
    lens = doc_lengths(corpus, seed)
    rng = _rng(seed, "doc_tokens")
    docs = [_ids(rng, int(n), vocab, zipf) for n in lens]

    due = arrival_times(float(spec["arrivals"]["rate_per_s"]), seconds, seed)
    n_warm = int(spec.get("warmup", {}).get("requests", 0))
    span = np.arange(qs["len_min"], qs["len_max"] + 1)
    qrng = _rng(seed, "query_tokens")
    queries = [_ids(qrng, int(m), vocab, zipf) for part, k in
               (("query_lengths", len(due)), ("warm_query_lengths", n_warm))
               for m in _rng(seed, part).permutation(np.resize(span, k))]
    pop = Popularity(len(docs), cand, seed)
    crng = _rng(seed, "candidates")
    k = int(cand["per_request"])
    # warm-up requests come from the popularity at the window's start
    warm = [Request(-1, 0.0, queries[len(due) + i], pop.draw(crng, k, 0.0))
            for i in range(n_warm)]
    reqs = [Request(i, float(t), queries[i], pop.draw(crng, k, float(t)))
            for i, t in enumerate(due)]
    return Traffic(docs=docs, requests=reqs, warm=warm)


def pack_query(q: np.ndarray, max_len: int):
    """``[CLS] q [SEP]`` padded -> (tokens [Lq] int32, valid [Lq] bool)."""
    packed = np.concatenate([[CLS], q, [SEP]])[:max_len]
    tok = np.full(max_len, PAD, np.int32)
    tok[:len(packed)] = packed
    return tok, np.arange(max_len) < len(packed)


def pack_doc(d: np.ndarray, max_len: int):
    """``d [SEP]`` (truncated) padded -> (tokens [Ld] int32, valid)."""
    packed = np.concatenate([d[:max_len - 1], [SEP]])
    tok = np.full(max_len, PAD, np.int32)
    tok[:len(packed)] = packed
    return tok, np.arange(max_len) < len(packed)
