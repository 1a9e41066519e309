"""The traffic generator: the same output for the same seed, the stated
length ranges, the same sizes in every batch, and group sharing."""
import json

import numpy as np
from tiny import ROOT

from bench.lib import traffic

V = 151_936


def mix(name):
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json").read_text())


def test_learn_batch_is_a_function_of_seed_and_step():
    t = mix("gepo_learn_g4")
    a = traffic.learn_batch(t, V, 2 ** 33 + 5, 1)
    b = traffic.learn_batch(t, V, 2 ** 33 + 5, 1)
    c = traffic.learn_batch(t, V, 2 ** 33 + 6, 1)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
    assert not np.array_equal(a["tokens"], c["tokens"])


def test_learn_batch_shapes_ranges_and_groups():
    t = mix("gepo_learn_g4")
    g, n = t["group_size"], t["prompts"]
    b = traffic.learn_batch(t, V, 12345, 0)
    rows = g * n
    assert b["tokens"].shape == (rows, t["width"])
    assert b["mask"].shape == b["sampler_lp"].shape == (rows, t["width"] - 1)
    comp = b["mask"].sum(-1).astype(int)
    plen = b["lengths"] - comp
    assert plen.min() >= 64 and plen.max() <= 256
    assert sorted(set(plen)) == sorted(traffic.prompt_lengths(t))
    assert comp.min() >= 1 and comp.max() <= 768
    for i in range(rows):
        row = b["tokens"][i, :b["lengths"][i]]
        assert row.min() >= 3 and row.max() < V          # no special ids
        assert (b["tokens"][i, b["lengths"][i]:] == 0).all()
        # targets of the completion only
        assert b["mask"][i, plen[i] - 1:b["lengths"][i] - 1].all()
    for gi in range(n):
        grp = slice(gi * g, (gi + 1) * g)
        p = plen[gi * g]
        assert (plen[grp] == p).all()
        assert (b["tokens"][grp, :p] == b["tokens"][gi * g, :p]).all()
        r = b["rewards"][grp]
        assert 0 < r.sum() < g                           # mixed rewards


def test_every_batch_holds_the_same_sizes():
    t = mix("gepo_learn_g4")
    sizes = set()
    for s in (1, 2 ** 32 + 1):
        for k in (0, 3):
            b = traffic.learn_batch(t, V, s, k)
            comp = b["mask"].sum(-1).astype(int)
            sizes.add((int(b["lengths"].sum()), tuple(sorted(comp)),
                       tuple(sorted(b["lengths"] - comp))))
    assert len(sizes) == 1
    # lognormal quantiles: median about 192, a few rows at the 768 cap
    c = traffic.completion_lengths(t)
    assert sorted(c)[len(c) // 2] in range(180, 205) and max(c) == 768


def test_rollout_requests():
    t = mix("gepo_rollout")
    g = t["group_size"]
    a = traffic.rollout_requests(t, V, 99, 2)
    b = traffic.rollout_requests(t, V, 99, 2)
    assert [r["rid"] for r in a] == list(range(2 * 64, 3 * 64))
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x["prompt"], y["prompt"])
        assert x["max_new"] == y["max_new"]
    for i in range(0, len(a), g):
        grp = a[i:i + g]
        assert all(np.array_equal(r["prompt"], grp[0]["prompt"]) for r in grp)
    assert sorted(r["max_new"] for r in a) == sorted(
        traffic.completion_lengths(t))
    assert all(r["prompt"].size + r["max_new"] <= 1024 for r in a)
