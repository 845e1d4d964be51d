"""The traffic generator: seeded, inside its clips, the same schedule
for every seed."""
import json
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.core.traffic import Traffic, _quantiles  # noqa: E402

MIXES = sorted({ROOT / "bench" / "traffic" / f"{w['traffic']}.json"
                for w in json.loads((ROOT / "BENCHMARK.json").read_text())
                ["workloads"]})
BIG_SEED = 2 ** 31 + 12345


def _limits():
    """(max_len, vocab) of each mix's configuration, per BENCHMARK.json."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    out = {}
    for w in bench["workloads"]:
        c = next(c for c in bench["configs"] if c["name"] == w["config"])
        cfg = json.loads((ROOT / c["file"]).read_text())
        vocab = cfg.get("vocab_size")
        out[w["traffic"]] = (cfg["deployment"]["max_len"], vocab)
    return out


@pytest.mark.parametrize("mix_path", MIXES, ids=lambda p: p.stem)
def test_mix_lengths_in_clips_and_under_max_len(mix_path):
    mix = json.loads(mix_path.read_text())
    max_len, vocab = _limits()[mix_path.stem]
    tr = Traffic(mix, BIG_SEED, vocab=vocab, max_len=max_len)
    for i in range(2 * tr.n):
        prompt, n_out = tr.request(i)
        assert mix["prompt"]["min"] <= len(prompt) <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= n_out <= mix["output"]["max"]
        assert len(prompt) + n_out <= max_len
        assert min(prompt) >= 1 and max(prompt) < vocab


@pytest.mark.parametrize("mix_path", MIXES, ids=lambda p: p.stem)
def test_mix_reproducible_from_seed(mix_path):
    mix = json.loads(mix_path.read_text())
    max_len, vocab = _limits()[mix_path.stem]
    a = Traffic(mix, 7, vocab=vocab, max_len=max_len)
    b = Traffic(mix, 7, vocab=vocab, max_len=max_len)
    c = Traffic(mix, 8, vocab=vocab, max_len=max_len)
    assert [a.request(i) for i in range(5)] == [b.request(i) for i in range(5)]
    assert [a.request(i) for i in range(5)] != [c.request(i) for i in range(5)]
    if mix["loop"] == "open":
        assert [a.arrival(i) for i in range(5)] == \
            [b.arrival(i) for i in range(5)]


@pytest.mark.parametrize("mix_path", MIXES, ids=lambda p: p.stem)
def test_every_seed_draws_the_same_sizes(mix_path):
    mix = json.loads(mix_path.read_text())
    max_len, vocab = _limits()[mix_path.stem]
    blocks = []
    for seed in (1, 2, BIG_SEED):
        tr = Traffic(mix, seed, vocab=vocab, max_len=max_len)
        blocks.append((Counter(tr.sizes(i)[0] for i in range(tr.n)),
                       Counter(tr.sizes(i)[1] for i in range(tr.n))))
    assert blocks[0] == blocks[1] == blocks[2]


def test_open_loop_rate_is_exact_per_block():
    mix = {"loop": "open", "rate_per_s": 4.0, "sizes": 128, "schedule_seed": 1,
           "prompt": {"dist": "uniform", "min": 1, "max": 10},
           "output": {"dist": "uniform", "min": 1, "max": 10}}
    tr = Traffic(mix, 3, vocab=100, max_len=64)
    arr = np.array([tr.arrival(i) for i in range(2 * tr.n)])
    assert np.all(np.diff(arr) > 0)
    q = (np.arange(128) + 0.5) / 128
    assert arr[tr.n - 1] == pytest.approx(np.sum(-np.log1p(-q)) / 4.0)
    assert tr.n / arr[tr.n - 1] == pytest.approx(4.0, rel=0.02)


def test_lognormal_quantiles_median_and_clip():
    vals = _quantiles({"dist": "lognormal", "median": 256, "sigma": 0.8,
                       "min": 16, "max": 768}, 255)
    assert vals[127] == 256
    assert vals.min() >= 16 and vals.max() == 768


def test_rejects_mix_longer_than_max_len():
    mix = {"loop": "closed", "clients": 2, "sizes": 8, "schedule_seed": 1,
           "prompt": {"dist": "uniform", "min": 10, "max": 100},
           "output": {"dist": "uniform", "min": 10, "max": 100}}
    with pytest.raises(ValueError):
        Traffic(mix, 1, vocab=50, max_len=150)


def test_schedule_seed_fixes_sizes_and_arrivals_not_tokens():
    mix = {"loop": "open", "rate_per_s": 2.0, "sizes": 16, "schedule_seed": 8,
           "prompt": {"dist": "uniform", "min": 1, "max": 50},
           "output": {"dist": "uniform", "min": 1, "max": 50}}
    a = Traffic(mix, 1, vocab=100, max_len=128)
    b = Traffic(mix, BIG_SEED, vocab=100, max_len=128)
    assert [a.sizes(i) for i in range(40)] == [b.sizes(i) for i in range(40)]
    assert [a.arrival(i) for i in range(40)] == \
        [b.arrival(i) for i in range(40)]
    assert a.request(0)[0] != b.request(0)[0]
