"""The ``tokens`` dataset: the seeded generator
(``benchmark/datagen/tokens.py``), the launcher's loader
(``data/datasets.py:load_tokens``) and the layout the round gathers
from; and the configuration's operation counts
(``benchmark/flops/olmo_hybrid.py``)."""
import numpy as np
import pytest

from benchmark.datagen import tokens
from benchmark.flops import olmo_hybrid as flops
from fedtorch_tpu.data.datasets import load_tokens

SIZES = dict(clients=5, rows_per_client=7, seq_len=33, vocab_size=257,
             test_rows=3)


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_generator_is_a_pure_function_of_the_seed(tmp_path, seed):
    a = tokens.write(str(tmp_path / "a"), seed, **SIZES)
    b = tokens.write(str(tmp_path / "b"), seed, **SIZES)
    c = tokens.write(str(tmp_path / "c"), seed + 1, **SIZES)
    xa, xb, xc = (load_tokens(d).train_x for d in (a, b, c))
    assert np.array_equal(xa, xb) and not np.array_equal(xa, xc)
    assert xa.dtype == np.int32 and xa.shape == (35, 33)
    assert 0 <= xa.min() and xa.max() < SIZES["vocab_size"]


def test_loader_gives_each_client_its_rows(tmp_path):
    from fedtorch_tpu.data.batching import stack_partitions
    s = load_tokens(tokens.write(str(tmp_path), 1, **SIZES))
    assert [len(p) for p in s.client_partitions] == [7] * 5
    assert s.train_y.tolist() == np.repeat(np.arange(5), 7).tolist()
    assert s.test_x.shape == (3, 33) and s.test_y.shape == (3,)
    data = stack_partitions(s.train_x, s.train_y, s.client_partitions)
    assert data.x.shape == (5, 7, 33) and data.y.shape == (5, 7)
    assert np.array_equal(data.x[2], s.train_x[14:21])


def test_clients_differ_and_frequencies_are_skewed(tmp_path):
    big = dict(SIZES, rows_per_client=64, seq_len=256)
    s = load_tokens(tokens.write(str(tmp_path), 9, **big))
    top = []
    for p in s.client_partitions:
        ids, n = np.unique(s.train_x[p], return_counts=True)
        top.append(int(ids[np.argmax(n)]))
        assert n.max() / n.sum() > 5.0 / big["vocab_size"]
    # half of every client's law is the common one: its head is shared
    assert len(set(top)) < len(top)
    counts = [np.bincount(s.train_x[p].ravel(), minlength=257)
              for p in s.client_partitions]
    assert not np.array_equal(np.argsort(counts[0])[-8:],
                              np.argsort(counts[1])[-8:])


def test_missing_files_are_named(tmp_path):
    with pytest.raises(FileNotFoundError, match="tokens"):
        load_tokens(str(tmp_path))


def test_operation_counts_of_the_configuration():
    s = flops.spec()
    assert s["layer_types"] == ["linear_attention"] * 3 + ["full_attention"]
    t = s["seq_len"]
    macs = flops.forward_matmul_macs_per_token(s)
    # every weight matrix once: the parameters less the embedding's
    # rows, the convolution, the norms and the two head-wise vectors
    assert macs == 880_512_000
    total = flops.train_flops_per_image(s)
    assert 6 * macs * t < total < 1.03 * 6 * macs * t
    # the mixers' own work, of the mathematics: both far under a
    # millisecond of the chip's peaks a call
    assert flops.delta_rule_flops(t, s) == 3 * t * 30 * 7 * 96 * 192
    assert flops.delta_rule_bytes(t, s) / 819e9 \
        > flops.delta_rule_flops(t, s) / 197e12
    assert flops.attention_flops(t, s) / 197e12 \
        > flops.attention_bytes(t, s) / 819e9
