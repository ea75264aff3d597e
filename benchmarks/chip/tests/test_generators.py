"""The traffic generators: one seed, one schedule; the mixes' weights."""

import collections

import numpy as np

import generators
import spec


def _chat():
    return spec.load_cell("minicpm-2b.serve.chat").traffic


def test_same_seed_same_schedule_other_seed_same_work():
    mix = _chat()
    a = generators.chat_schedule(mix, 122753, 2 ** 33 + 5, 45.0)
    b = generators.chat_schedule(mix, 122753, 2 ** 33 + 5, 45.0)
    c = generators.chat_schedule(mix, 122753, 7, 45.0)
    assert [(r.due, r.n_out, r.prompt.tobytes()) for r in a] == \
        [(r.due, r.n_out, r.prompt.tobytes()) for r in b]
    # another seed: other tokens, the same arrivals and sizes in order
    assert [(r.due, len(r.prompt), r.n_out) for r in a] == \
        [(r.due, len(r.prompt), r.n_out) for r in c]
    assert any(r.prompt.tobytes() != q.prompt.tobytes()
               for r, q in zip(a, c))


def test_prompt_lengths_follow_the_grid_weights():
    mix = dict(_chat(), rate_per_s=20.0)
    reqs = generators.chat_schedule(mix, 122753, 3, 50.0)  # 1000 requests
    counts = collections.Counter(len(r.prompt) for r in reqs)
    for plen, w in mix["prompt_len"].items():
        assert abs(counts[int(plen)] / len(reqs) - w) <= 1 / len(reqs)
    outs = np.array([r.n_out for r in reqs])
    assert outs.min() >= 8 and outs.max() <= 256
    assert abs(np.median(outs) - 64) <= 1
    assert all(len(r.prompt) + r.n_out <= mix["max_len"] for r in reqs)
    gaps = np.diff([0.0] + [r.due for r in reqs])
    assert abs(gaps.mean() - 1 / 20.0) < 0.05 / 20.0


def test_corpus_packs_into_the_rows_the_job_asks_for():
    job = spec.load_cell("mamba2-370m.train.commit").traffic
    job = dict(job, corpus=dict(job["corpus"], rows=64), seq_len=256)
    a = generators.corpus(job, 50280, 2 ** 40)
    b = generators.corpus(job, 50280, 2 ** 40)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    rows = generators.pack(a, 256)
    assert rows.shape == (64, 256) or rows.shape[0] >= 64
    assert rows.min() >= 0 and rows.max() < 50280


def test_sub_seeds_fit_31_bits_for_any_run_seed():
    for s in (0, 1, 2 ** 31 + 7, 2 ** 63 - 1):
        v = generators.sub_seed(s, "init")
        assert 0 <= v < 2 ** 31
    assert generators.sub_seed(5, "init") != generators.sub_seed(5, "corpus")
