"""The generator: the same seed gives the same load, another seed the same
sizes in another order."""
import json
import os

import numpy as np

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


def test_serve_requests_offer_every_seed_the_same_sizes():
    mix = _mix("chat-closed")
    k = mix["size_set"]
    a = traffic.serve_requests(mix, 50304, seed=1)
    b = traffic.serve_requests(mix, 50304, seed=1)
    c = traffic.serve_requests(mix, 50304, seed=2 ** 31 + 9)
    assert a == b and len(a) == mix["n_requests"]

    def sizes(reqs, lo):
        return sorted((len(r.prompt), r.max_new_tokens)
                      for r in reqs[lo:lo + k])

    assert sizes(a, 0) == sizes(c, 0) == sizes(a, k) == sizes(c, 3 * k)
    assert [len(r.prompt) for r in a[:k]] != [len(r.prompt) for r in c[:k]]
    assert a[0].prompt != a[k].prompt  # same size again, other tokens
    p, o = mix["prompt_len"], mix["output_len"]
    assert all(p["min"] <= len(r.prompt) <= p["max"]
               and o["min"] <= r.max_new_tokens <= o["max"] for r in a)
