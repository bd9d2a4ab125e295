"""The one general generator: both data kinds, from any whole-number seed."""

import numpy as np

from benchmarks.harness import datagen
from benchmarks.harness.spec import load_module

# the objective a traffic file gets that names none (defaults.json)
CLASS_LABEL = ({"name": "class_label"},
               load_module("reference/objectives", "class_label").targets)


def make_data(inp, job, seed):
    objective, targets = CLASS_LABEL
    return datagen.make_data(inp, job, seed, targets, objective)


def test_seeds_beyond_32_bits_fold_to_distinct_31_bit_seeds():
    seeds = [0, 1, 2**31 - 1, 2**31, 2**31 + 1, 2**32 + 5, 4000000123]
    folded = [datagen.seed31(s) for s in seeds]
    assert len(set(folded)) == len(seeds) and all(0 <= f < 2**31 for f in folded)
    assert datagen.seed31(4000000123) == datagen.seed31(4000000123)


def test_token_mix_lengths_padding_and_learnable_labels():
    job = {"clients": 3, "val_examples": 2, "batch": 4, "train_examples": [4, 8]}
    inp = {"kind": "tokens", "seq": 16, "vocab": 50, "classes": 4,
           "min_len_frac": 0.5}
    x, y, xv, yv = make_data(inp, job, 2**31 + 9)
    assert x.shape == (3, 8, 16) and y.shape == (3, 8) and xv.shape == (3, 2, 16)
    x = np.asarray(x)
    lengths = (x > 0).sum(-1)
    assert lengths.min() >= 8 and lengths.max() <= 16 and x.max() < 50
    assert ((x[..., 0] + x[..., 1]) % 4 == np.asarray(y)).all()
    again = make_data(inp, job, 2**31 + 9)[0]
    assert (np.asarray(again) == x).all()
    assert datagen.client_rows(job) == [4, 8, 4]


def test_image_mix_and_label_sorted_shards():
    job = {"clients": 10, "val_examples": 4, "batch": 4, "train_examples": 8}
    inp = {"kind": "images", "hw": 8, "channels": 3, "classes": 10}
    x, y, _, _ = make_data(inp, job, 7)
    assert x.shape == (10, 8, 8, 8, 3) and len(np.unique(np.asarray(y))) > 5
    _, y, _, yv = make_data(dict(inp, label_shards=2), job, 7)
    y = np.asarray(y)
    for client in range(10):
        # a client holds two consecutive classes that follow its index
        assert set(np.unique(y[client])) <= {client, (client + 1) % 10}
        assert set(np.unique(np.asarray(yv)[client])) <= {client, (client + 1) % 10}
