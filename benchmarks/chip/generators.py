"""The general generators that read a traffic file's parameters.

Every seed gets the same work: the same multiset of sizes and gaps, drawn
at fixed quantiles of the file's distributions.  The seed picks the token
contents (and, for a training corpus, the order of the documents; a chat
mix's order is its own).  So runs with different seeds differ in data,
not in how much there is to do.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np

EOS = 0            # reserved ids below FIRST_TOKEN, as the program's corpus
FIRST_TOKEN = 3


def sub_seed(seed: int, purpose: str) -> int:
    """A 31-bit seed for one purpose, from a run seed of any size."""
    words = [int(b) for b in purpose.encode()]
    ss = np.random.SeedSequence([int(seed) & (2 ** 64 - 1), *words])
    return int(ss.generate_state(1)[0] & 0x7FFFFFFF)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_sizes(n: int, median: float, sigma: float, lo: int, hi: int
                    ) -> np.ndarray:
    """``n`` sizes at the midpoints of n equal-probability strata of a
    lognormal, clipped to [lo, hi]."""
    from statistics import NormalDist

    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    return np.clip(np.round(median * np.exp(sigma * z)), lo, hi).astype(int)


def zipf_tokens(rng, n: int, vocab: int, a: float) -> np.ndarray:
    """Token ids with Zipf(a) frequencies over [FIRST_TOKEN, vocab)."""
    ranks = np.arange(1, vocab - FIRST_TOKEN + 1, dtype=np.float64)
    cdf = np.cumsum(ranks ** -a)
    cdf /= cdf[-1]
    ids = np.searchsorted(cdf, rng.random(n), side="right")
    return (np.minimum(ids, vocab - FIRST_TOKEN - 1) + FIRST_TOKEN).astype(
        np.int32)


# ------------------------------------------------------------- training
def corpus(job: dict, vocab: int, seed: int) -> Dict[str, np.ndarray]:
    """Raw documents that pack into ``rows`` rows of ``seq_len`` tokens:
    columns doc_id, tokens (PAD-filled to the longest), length."""
    c = job["corpus"]
    seq = job["seq_len"]
    need = c["rows"] * seq
    # a fixed multiset of document lengths, enough to fill the rows
    n_docs = int(np.ceil(need / c["doc_len_median"] * 1.2)) + 1
    lengths = lognormal_sizes(n_docs, c["doc_len_median"],
                              c["doc_len_sigma"], 16, 4 * seq)
    rng = np.random.default_rng(sub_seed(seed, "corpus"))
    lengths = lengths[rng.permutation(n_docs)]
    keep = int(np.searchsorted(np.cumsum(lengths + 1), need)) + 1
    lengths = lengths[:keep]
    width = int(lengths.max())
    tokens = np.full((keep, width), 2, np.int32)  # PAD
    flat = zipf_tokens(rng, int(lengths.sum()), vocab, c["zipf_a"])
    off = 0
    for i, n in enumerate(lengths):
        tokens[i, :n] = flat[off:off + n]
        off += n
    return {"doc_id": np.arange(keep, dtype=np.int64), "tokens": tokens,
            "length": lengths.astype(np.int32)}


def pack(docs: Dict[str, np.ndarray], seq_len: int) -> np.ndarray:
    """Documents joined with EOS after each, cut into rows of seq_len; the
    ragged tail is dropped."""
    parts = []
    for row, n in zip(docs["tokens"], docs["length"]):
        parts.append(row[:n])
        parts.append(np.array([EOS], np.int32))
    flat = np.concatenate(parts)
    rows = len(flat) // seq_len
    return flat[:rows * seq_len].reshape(rows, seq_len)


# -------------------------------------------------------------- serving
@dataclasses.dataclass
class Request:
    rid: int
    due: float          # seconds after the window opens
    prompt: np.ndarray
    n_out: int


def chat_schedule(mix: dict, vocab: int, seed: int, horizon_s: float
                  ) -> List[Request]:
    """Open-loop Poisson arrivals at ``rate_per_s`` up to ``horizon_s``:
    the gaps are the exponential's quantiles, the prompt lengths follow
    the grid's weights exactly (largest remainders), the output lengths a
    clipped lognormal's quantiles, shuffled once by the mix's own
    ``schedule_seed``.  The run's seed draws the prompts' tokens: every
    seed gets the same arrivals and sizes in the same order, because the
    order alone moves the tail of the gaps between tokens by a tenth (the
    share of gaps that carry an admit sits near 5%)."""
    rate = float(mix["rate_per_s"])
    n = int(np.ceil(rate * horizon_s))
    rng = np.random.default_rng(mix["schedule_seed"])
    gaps = -np.log1p(-_quantiles(n)) / rate
    due = np.cumsum(rng.permutation(gaps))

    grid = sorted((int(k), float(w)) for k, w in mix["prompt_len"].items())
    want = np.array([w for _, w in grid]) / sum(w for _, w in grid) * n
    counts = np.floor(want).astype(int)
    for i in np.argsort(-(want - counts))[:n - counts.sum()]:
        counts[i] += 1
    plens = np.repeat([p for p, _ in grid], counts)[rng.permutation(n)]

    o = mix["output_len"]
    outs = lognormal_sizes(n, o["median"], o["sigma"], o["min"], o["max"])
    outs = outs[rng.permutation(n)]
    outs = np.minimum(outs, mix["max_len"] - plens)

    tokens = zipf_tokens(np.random.default_rng(sub_seed(seed, "chat")),
                         int(plens.sum()), vocab, 1.1)
    reqs, off = [], 0
    for i in range(n):
        p = int(plens[i])
        reqs.append(Request(i, float(due[i]), tokens[off:off + p],
                            int(outs[i])))
        off += p
    return reqs
