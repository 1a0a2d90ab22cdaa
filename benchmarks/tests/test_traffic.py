"""The generator: the same seed gives the same load, another seed the same
sizes in another order."""
import json
import os

import numpy as np
import pytest

from benchmarks.harness import spec, traffic


def _mix(name):
    with open(os.path.join(spec.BENCH_DIR, "traffic", name + ".json")) as f:
        return json.load(f)


def test_train_batches_repeat_per_seed_and_rows_differ():
    mix = dict(_mix("train-1024"), seq_len=32, pool_sequences=64)
    a = traffic.TrainBatches(mix, 500, 8, seed=2 ** 31 + 5)
    b = traffic.TrainBatches(mix, 500, 8, seed=2 ** 31 + 5)
    c = traffic.TrainBatches(mix, 500, 8, seed=6)
    first = [x for x, _ in zip(iter(a), range(3))]
    for i in range(3):
        assert (first[i] == b.batch(i)).all()
        assert first[i].shape == (8, 33) and first[i].dtype == np.int32
        assert len({row.tobytes() for row in first[i]}) == 8
    assert not (a.batch(0) == c.batch(0)).all()
    # bigram structure: every token is one of its predecessor's successors
    table = np.random.default_rng(mix["stream"]["table_seed"]).integers(
        0, 500, size=(500, mix["stream"]["branching"]))
    row = a.batch(0)[0]
    assert all(row[t + 1] in table[row[t]] for t in range(32))


SERVE_MIXES = ("chat-closed", "doc-closed", "long-closed", "agent-closed")


@pytest.mark.parametrize("name", SERVE_MIXES)
def test_serve_requests_offer_every_seed_the_same_sizes(name):
    mix = _mix(name)
    k, n = mix["size_set"], mix["n_requests"]
    a = traffic.serve_requests(mix, 320, seed=1)
    b = traffic.serve_requests(mix, 320, seed=1)
    c = traffic.serve_requests(mix, 320, seed=2 ** 31 + 9)
    assert a == b and len(a) == n
    assert [r.index for r in a] == list(range(n))

    def sizes(reqs, lo):
        return sorted((len(r.prompt), r.max_new_tokens)
                      for r in reqs[lo:lo + k])

    # the set is walked round and round, to the list's last whole walk
    last = (n // k - 1) * k
    assert sizes(a, 0) == sizes(c, 0) == sizes(a, k) == sizes(c, 3 * k) \
        == sizes(a, last) == sizes(c, last)
    assert [len(r.prompt) for r in a[:k]] != [len(r.prompt) for r in c[:k]]
    assert a[0].prompt != a[k].prompt  # same size again, other tokens
    p, o = mix["prompt_len"], mix["output_len"]
    assert all(p["min"] <= len(r.prompt) <= p["max"]
               and o["min"] <= r.max_new_tokens <= o["max"] for r in a)


def test_every_walk_of_the_set_has_an_order_of_its_own():
    """PR 39: ``chat-closed`` lists 16384 requests (ramp and a traced run's
    52 s window at 3.6 times gpt2-medium's pace) and every walk of the 64
    sizes has its own order, so that a run averages over orders where one
    order a seed decided its tails. A longer list walks the same orders
    further: what a seed offers first does not depend on the list's length."""
    mix = _mix("chat-closed")
    k = mix["size_set"]
    assert mix["n_requests"] == 16384
    for seed in (1, 2 ** 31 + 9):
        reqs = traffic.serve_requests(mix, 50304, seed)
        walks = [[(len(r.prompt), r.max_new_tokens) for r in reqs[lo:lo + k]]
                 for lo in range(0, len(reqs), k)]
        assert all(sorted(w) == sorted(walks[0]) for w in walks)
        assert len({tuple(w) for w in walks}) == len(walks)
        short = traffic.serve_requests(dict(mix, n_requests=2048), 50304,
                                       seed)
        assert [(len(r.prompt), r.max_new_tokens) for r in short] \
            == sum(walks[:2048 // k], [])
