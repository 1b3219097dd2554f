"""The seeded generators: pure functions of the seed, in the formats
the launcher's own loaders read, at the datasets' real sizes."""
import numpy as np
import pytest

from benchmark.datagen import cifar10
from fedtorch_tpu.data.datasets import load_cifar


def test_cifar10_files_read_by_the_launchers_loader(tmp_path):
    cifar10.write(str(tmp_path), 2**31 + 5, 50_000, 10_000)
    s = load_cifar("cifar10", str(tmp_path))
    assert s.train_x.shape == (50_000, 32, 32, 3)
    assert s.test_x.shape == (10_000, 32, 32, 3)
    assert s.train_x.dtype == np.float32
    # every class the same count, so no shard size depends on the seed
    assert np.bincount(s.train_y).tolist() == [5_000] * 10


@pytest.mark.parametrize("gen,loader,name", [
    (cifar10, load_cifar, "cifar10")])
def test_a_pure_function_of_the_seed(tmp_path, gen, loader, name):
    a, b, c = (tmp_path / d for d in "abc")
    gen.write(str(a), 11, 500, 100)
    gen.write(str(b), 11, 500, 100)
    gen.write(str(c), 12, 500, 100)
    xa, xb, xc = (loader(name, str(d)).train_x for d in (a, b, c))
    assert np.array_equal(xa, xb)
    assert not np.array_equal(xa, xc)
