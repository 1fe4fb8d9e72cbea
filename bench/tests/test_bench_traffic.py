"""The traffic generator: deterministic in its seed, inside its declared ranges,
and the same set of sizes for every seed, in another order."""
import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from benchlib import traffic as TR

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"
SERVE = sorted(p.stem for p in TRAFFIC.glob("*.json")
               if json.loads(p.read_text())["kind"] == "serve")
TRAIN = sorted(p.stem for p in TRAFFIC.glob("*.json")
               if json.loads(p.read_text())["kind"] == "train")
BIG = 3_000_000_017  # seeds past 32 bits


def mix(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


def sizes(reqs):
    return [(r.prompt.size, r.max_new, r.warmup) for r in reqs]


@pytest.mark.parametrize("name", SERVE)
def test_serve_is_deterministic_in_its_seed(name):
    a, b = TR.serve_requests(mix(name), BIG, 32768), TR.serve_requests(mix(name), BIG, 32768)
    assert sizes(a) == sizes(b)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = TR.serve_requests(mix(name), BIG + 1, 32768)
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("name", SERVE)
def test_serve_keeps_its_ranges(name):
    m = mix(name)
    reqs = TR.serve_requests(m, 5, 32768)
    main = [r for r in reqs if not r.warmup]
    warm = [r for r in reqs if r.warmup]
    assert len(main) == m["requests"] and len(warm) == m.get("warmup", {}).get("requests", 0)
    assert reqs[: len(warm)] == warm  # warm-up requests come first
    for key, attr in (("prompt", lambda r: r.prompt.size), ("output", lambda r: r.max_new)):
        lo, hi = m[key]["min"], m[key]["max"]
        assert all(lo <= attr(r) <= hi for r in main)
    assert all(0 <= int(t) < 32768 for r in reqs for t in r.prompt[:50])
    w = m["warmup"]["output"]
    assert all(w["min"] <= r.max_new <= w["max"] for r in warm)


@pytest.mark.parametrize("name", SERVE)
def test_every_seed_gets_the_same_sizes_in_each_block(name):
    m = mix(name)
    k = m["block"]
    a = [r for r in TR.serve_requests(m, 1, 32768) if not r.warmup]
    b = [r for r in TR.serve_requests(m, BIG, 32768) if not r.warmup]
    for i in range(0, len(a) - k + 1, k):
        assert Counter(r.prompt.size for r in a[i:i + k]) == \
            Counter(r.prompt.size for r in b[i:i + k])
        assert Counter(r.max_new for r in a[i:i + k]) == Counter(r.max_new for r in b[i:i + k])
    assert [r.prompt.size for r in a] != [r.prompt.size for r in b]


def test_quantile_grid():
    g = TR.quantile_grid({"dist": "lognormal", "median": 100, "sigma": 0.5, "min": 50,
                          "max": 200}, 101)
    assert g[50] == 100 and g.min() >= 50 and g.max() <= 200 and np.all(np.diff(g) >= 0)
    assert list(TR.quantile_grid({"dist": "linspace", "min": 8, "max": 64}, 3)) == [8, 36, 64]


@pytest.mark.parametrize("name", TRAIN)
def test_train_rows_are_deterministic_and_all_differ(name):
    m = dict(mix(name), seq_len=64, batch=4)
    a, b = TR.train_batch(m, BIG, 3, 32768), TR.train_batch(m, BIG, 3, 32768)
    assert np.array_equal(a["tokens"], b["tokens"]) and a["tokens"].shape == (4, 64)
    assert np.array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
    assert len({r.tobytes() for r in a["tokens"]}) == 4
    assert not np.array_equal(a["tokens"], TR.train_batch(m, BIG, 4, 32768)["tokens"])
    assert a["tokens"].min() >= 0 and a["tokens"].max() < 32768
