"""Seeded traffic: reproducible, on the ladder, within max_len, the same
work for every seed, and a backlog that never runs dry."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from servebench import spec, traffic  # noqa: E402

CELLS = ["granite_3_8b.chat", "granite_3_8b.batch", "starcoder2_15b.code",
         "starcoder2_15b.batch"]
BIG = 2**31 + 123_456_789


def gen_for(name, seed, seconds=45.0):
    """The generator of traffic file ``name``, whose configuration is the
    name's part before the first dot."""
    c = spec.config(name.split(".")[0])
    t = spec.traffic(name)
    return traffic.Generator(t, seed, seconds, c["serving"]["max_len"],
                             c["vocab_size"]), c, t


def requests(g, n=200):
    if g.is_backlog:
        return [g.take(0.0) for _ in range(n)]
    return g.schedule()


@pytest.mark.parametrize("cell", CELLS)
def test_same_seed_same_requests(cell):
    a = requests(gen_for(cell, BIG)[0])
    b = requests(gen_for(cell, BIG)[0])
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert x.due == y.due and x.out_tokens == y.out_tokens
        np.testing.assert_array_equal(x.prompt, y.prompt)
    c = requests(gen_for(cell, BIG + 1)[0])
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("cell", CELLS)
def test_lengths_on_ladder_and_within_max_len(cell):
    g, c, t = gen_for(cell, 7)
    ladder = set(t["prompt"]["ladder"])
    for r in requests(g, 2000):
        assert len(r.prompt) in ladder
        assert t["output"]["min"] <= r.out_tokens <= t["output"]["max"]
        assert len(r.prompt) + r.out_tokens <= c["serving"]["max_len"] - 1
        assert r.prompt.dtype == np.int32
        assert 0 <= r.prompt.min() and r.prompt.max() < c["vocab_size"]


@pytest.mark.parametrize("cell", CELLS)
def test_every_seed_gets_the_same_schedule(cell):
    """Seeds differ in token ids only: the same lengths, in the same order,
    due at the same times."""
    a, b = requests(gen_for(cell, 1)[0]), requests(gen_for(cell, BIG)[0])
    assert [(r.due, len(r.prompt), r.out_tokens) for r in a] == \
        [(r.due, len(r.prompt), r.out_tokens) for r in b]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("cell", ["granite_3_8b.chat", "starcoder2_15b.code"])
def test_poisson_fills_the_window(cell):
    g, _, t = gen_for(cell, 1)
    r = g.schedule()
    assert len(r) == round(t["rate_rps"] * 45.0)
    assert all(0.0 < x.due < 45.0 for x in r)
    assert all(x.due <= y.due for x, y in zip(r, r[1:]))


@pytest.mark.parametrize("cell", ["granite_3_8b.batch",
                                  "starcoder2_15b.batch"])
def test_backlog_never_runs_dry(cell):
    """A simulated loop: each iteration prefills one request and the
    harness tops the queue up before the next; the queue never empties."""
    g, _, t = gen_for(cell, 5)
    depth = g.backlog_depth()
    assert depth >= 32 + 16          # the ring and the most decode slots
    queue = []
    for _ in range(5000):
        while len(queue) < depth:
            queue.append(g.take(0.0))
        assert len(queue) == depth
        queue.pop(0)
    assert g.handed == 5000 + depth - 1


def test_refuses_traffic_longer_than_max_len():
    t = spec.traffic("granite_3_8b.chat")
    with pytest.raises(ValueError, match="max_len"):
        traffic.Generator(t, 0, 10.0, 1000, 49155)
