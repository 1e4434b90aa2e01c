"""The host-side traffic: reproducible from the seed, disjoint per group."""

import numpy as np

from traffic import MarkovTraffic

BIG = 2**31 + 12345


def make(seed=BIG, groups=4, pool_steps=8):
    return MarkovTraffic(vocab=50257, seq_len=64, global_batch=8,
                         groups=groups, seed=seed, pool_steps=pool_steps)


def test_same_seed_same_batches():
    a, b = make(), make()
    for step in (0, 1, 7):
        for k in ("tokens", "labels"):
            assert np.array_equal(a.batch(step)[k], b.batch(step)[k])


def test_other_seed_other_batches():
    assert not np.array_equal(make().batch(0)["tokens"],
                              make(BIG + 1).batch(0)["tokens"])


def test_labels_are_next_tokens_and_in_vocab():
    b = make().batch(3)
    assert b["tokens"].shape == b["labels"].shape == (8, 64)
    assert np.array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])
    assert b["tokens"].min() >= 0 and b["tokens"].max() < 50257


def test_every_row_differs_across_steps_and_groups():
    t = make()
    rows = np.concatenate([t.batch(s)["tokens"] for s in range(4)])
    assert len({r.tobytes() for r in rows}) == len(rows)


def test_each_group_has_its_own_stream():
    # group g's rows come from SeedSequence([seed, 1 + g]) alone
    t = make()
    w = t.walks(5)
    for g in range(4):
        rng = np.random.default_rng([BIG, 1 + g])
        firsts = rng.integers(0, 50257, size=(8, 2))
        assert np.array_equal(w[2 * g:2 * g + 2, 0], firsts[5])


def test_the_pool_repeats_after_pool_steps():
    t = make(pool_steps=4)
    assert np.array_equal(t.batch(1)["tokens"], t.batch(5)["tokens"])
    assert not np.array_equal(t.batch(1)["tokens"], t.batch(2)["tokens"])


def test_the_pool_size_does_not_change_its_first_rows_chain():
    # a longer pool draws other rows, but from the same chain
    a, b = make(pool_steps=4), make(pool_steps=8)
    assert np.array_equal(a.succ, b.succ) and np.array_equal(a.cum, b.cum)


def test_walks_follow_the_chain():
    t = make()
    w = t.walks(0)
    for row in w[:, :20]:
        for a, b in zip(row, row[1:]):
            assert b in t.succ[a]
