"""Dataset factory: the reference's 13-dataset zoo without torchvision.

Dispatch parity with ``get_dataset`` (``/root/reference/fedtorch/
components/datasets/prepare_data.py:124-163``): cifar10/cifar100/mnist/
fashion_mnist/stl10/emnist/emnist_full/shakespeare/synthetic/adult/
epsilon/rcv1/higgs/MSD.

Readers are pure numpy (idx, CIFAR pickle, TFF HDF5 via h5py, svmlight via
sklearn) against a local ``data_dir`` cache. Downloads are **gated**: the
training environment has zero egress, so loaders raise a clear error
naming the expected files/URLs instead of fetching implicitly; pass
``download=True`` to attempt a fetch where networking exists (the
reference downloads on rank 0 only, prepare_data.py:128 — here download
happens before the program starts, so no barrier is needed).

Every loader returns ``DatasetSplits`` of plain numpy arrays; federated
"natural" datasets (emnist/shakespeare/synthetic) also return per-client
partitions (SURVEY.md §2.7).
"""
from __future__ import annotations

import gzip
import os
import pickle
import struct
import tarfile
import urllib.request
from typing import List, NamedTuple, Optional

import numpy as np

from fedtorch_tpu.config import DataConfig
from fedtorch_tpu.data.synthetic import generate_synthetic

MEAN_STD = {
    # channel mean/std used by the reference transforms
    # (preprocess_toolkit.py:84-121 presets).
    "cifar10": ((0.4914, 0.4822, 0.4465), (0.2470, 0.2435, 0.2616)),
    "cifar100": ((0.5071, 0.4865, 0.4409), (0.2673, 0.2564, 0.2762)),
    "mnist": ((0.1307,), (0.3081,)),
    "fashion_mnist": ((0.286,), (0.353,)),
}

URLS = {
    "mnist": "http://yann.lecun.com/exdb/mnist/",
    "fashion_mnist": "http://fashion-mnist.s3-website.eu-central-1"
                     ".amazonaws.com/",
    "cifar10": "https://www.cs.toronto.edu/~kriz/cifar-10-python.tar.gz",
    "cifar100": "https://www.cs.toronto.edu/~kriz/cifar-100-python.tar.gz",
    "emnist": "https://storage.googleapis.com/tff-datasets-public/"
              "fed_emnist_digitsonly.tar.bz2",
    "emnist_full": "https://storage.googleapis.com/tff-datasets-public/"
                   "fed_emnist.tar.bz2",
    "shakespeare": "https://storage.googleapis.com/tff-datasets-public/"
                   "shakespeare.tar.bz2",
    "adult": "https://archive.ics.uci.edu/ml/machine-learning-databases/"
             "adult/",
    "stl10": "http://ai.stanford.edu/~acoates/stl10/stl10_binary.tar.gz",
    "libsvm": "https://www.csie.ntu.edu.tw/~cjlin/libsvmtools/datasets/",
}


class DatasetSplits(NamedTuple):
    train_x: np.ndarray
    train_y: np.ndarray
    test_x: np.ndarray
    test_y: np.ndarray
    # natural per-client partitions of the train arrays (index lists),
    # None for centrally-partitioned datasets
    client_partitions: Optional[List[np.ndarray]] = None
    # metadata for fair partitioning (adult)
    sensitive_values: Optional[np.ndarray] = None


def _missing(dataset: str, path: str) -> FileNotFoundError:
    return FileNotFoundError(
        f"{dataset}: expected local data at {path}. This environment has "
        f"no network egress; place the files there manually (source: "
        f"{URLS.get(dataset, URLS['libsvm'])}) or run with download=True "
        f"where networking exists.")


def _fetch(url: str, dest: str):
    os.makedirs(os.path.dirname(dest), exist_ok=True)
    urllib.request.urlretrieve(url, dest)


# -- MNIST-family (idx format) ---------------------------------------------

def _read_idx(path: str) -> np.ndarray:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        magic = struct.unpack(">I", f.read(4))[0]
        ndim = magic & 0xFF
        shape = struct.unpack(">" + "I" * ndim, f.read(4 * ndim))
        return np.frombuffer(f.read(), dtype=np.uint8).reshape(shape)


def load_mnist_family(dataset: str, data_dir: str,
                      download: bool = False) -> DatasetSplits:
    base = os.path.join(data_dir, dataset)
    names = {
        "train_x": "train-images-idx3-ubyte",
        "train_y": "train-labels-idx1-ubyte",
        "test_x": "t10k-images-idx3-ubyte",
        "test_y": "t10k-labels-idx1-ubyte",
    }

    def find(stem):
        for suffix in ("", ".gz"):
            p = os.path.join(base, stem + suffix)
            if os.path.exists(p):
                return p
        if download:
            p = os.path.join(base, stem + ".gz")
            _fetch(URLS[dataset] + stem + ".gz", p)
            return p
        raise _missing(dataset, os.path.join(base, stem + "[.gz]"))

    arrays = {k: _read_idx(find(v)) for k, v in names.items()}
    mean, std = MEAN_STD[dataset]
    norm = lambda x: ((x.astype(np.float32) / 255.0 - mean[0]) / std[0]
                      )[..., None]
    return DatasetSplits(
        train_x=norm(arrays["train_x"]),
        train_y=arrays["train_y"].astype(np.int64),
        test_x=norm(arrays["test_x"]),
        test_y=arrays["test_y"].astype(np.int64))


# -- CIFAR (pickle batches) -------------------------------------------------

def load_cifar(dataset: str, data_dir: str,
               download: bool = False) -> DatasetSplits:
    sub = "cifar-10-batches-py" if dataset == "cifar10" else "cifar-100-python"
    base = os.path.join(data_dir, sub)
    if not os.path.isdir(base):
        archive = os.path.join(data_dir, os.path.basename(URLS[dataset]))
        if os.path.exists(archive) or download:
            if not os.path.exists(archive):
                _fetch(URLS[dataset], archive)
            with tarfile.open(archive) as tf:
                tf.extractall(data_dir)
        else:
            raise _missing(dataset, base)

    def load_batch(name, label_key):
        with open(os.path.join(base, name), "rb") as f:
            d = pickle.load(f, encoding="bytes")
        return d[b"data"], np.asarray(d[label_key])

    if dataset == "cifar10":
        xs, ys = zip(*[load_batch(f"data_batch_{i}", b"labels")
                       for i in range(1, 6)])
        train_x, train_y = np.concatenate(xs), np.concatenate(ys)
        test_x, test_y = load_batch("test_batch", b"labels")
    else:
        train_x, train_y = load_batch("train", b"fine_labels")
        test_x, test_y = load_batch("test", b"fine_labels")

    mean, std = MEAN_STD[dataset]
    mean = np.asarray(mean, np.float32)
    std = np.asarray(std, np.float32)

    def norm(x):
        x = x.reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1)  # NHWC
        return (x.astype(np.float32) / 255.0 - mean) / std

    return DatasetSplits(train_x=norm(train_x),
                         train_y=train_y.astype(np.int64),
                         test_x=norm(test_x),
                         test_y=test_y.astype(np.int64))


# -- TFF federated HDF5 (EMNIST / Shakespeare) ------------------------------

def load_emnist(data_dir: str, full: bool = False,
                download: bool = False,
                allow_train_as_test: bool = False) -> DatasetSplits:
    """TFF fed_emnist HDF5: naturally-federated handwriting, 3383 writers
    (digits) / 3400 (full, 62 classes) (ref: federated_datasets.py:15-138).

    Some mirrors ship only the train archive. Substituting a slice of
    TRAIN rows as the test set silently reports train accuracy as test
    accuracy, so that fallback requires the explicit
    ``allow_train_as_test`` opt-in (``--allow_train_as_test``) and
    raises otherwise."""
    import h5py
    name = "fed_emnist" if full else "fed_emnist_digitsonly"
    base = os.path.join(data_dir, "emnist_full" if full else "emnist")
    train_p = os.path.join(base, f"{name}_train.h5")
    test_p = os.path.join(base, f"{name}_test.h5")
    url_key = "emnist_full" if full else "emnist"
    # the archive holds BOTH splits, so a missing test file (train-only
    # mirror) is also repaired by --download — the error below
    # advertises exactly that remediation
    for p in (train_p, test_p):
        if not os.path.exists(p):
            if download:
                archive = os.path.join(base, os.path.basename(URLS[url_key]))
                _fetch(URLS[url_key], archive)
                with tarfile.open(archive, "r:bz2") as tf:
                    tf.extractall(base)
            elif p == train_p:
                raise _missing(url_key, train_p)
            # test split missing without --download: the explicit
            # opt-in fallback below decides

    def read(path):
        xs, ys, parts = [], [], []
        with h5py.File(path, "r") as f:
            ex = f["examples"]
            offset = 0
            for client in sorted(ex.keys()):
                px = np.asarray(ex[client]["pixels"])
                py = np.asarray(ex[client]["label"])
                xs.append(px)
                ys.append(py)
                parts.append(np.arange(offset, offset + len(py)))
                offset += len(py)
        x = np.concatenate(xs).astype(np.float32)[..., None]
        y = np.concatenate(ys).astype(np.int64)
        return x, y, parts

    train_x, train_y, parts = read(train_p)
    if os.path.exists(test_p):
        test_x, test_y, _ = read(test_p)
    else:
        if not allow_train_as_test:
            raise FileNotFoundError(
                f"EMNIST test split missing: {test_p}. Refusing to "
                "silently substitute training rows as the test set — "
                "that reports train accuracy as test accuracy. Fetch "
                "the full archive (--download), or opt in explicitly "
                "with --allow_train_as_test if a train-slice pseudo "
                "test set is acceptable for this run.")
        import sys as _sys
        print(f"warning: {test_p} missing — using a 256-sample slice of "
              "the training data as the test set (allow_train_as_test "
              "opt-in)", file=_sys.stderr)
        test_x, test_y = train_x[:256], train_y[:256]
    return DatasetSplits(train_x, train_y, test_x, test_y,
                         client_partitions=parts)


# The 86-character TFF shakespeare vocabulary — char identity and order
# define token ids, so this must match the reference's intent
# (federated_datasets.py:339). Note the reference's literal is buggy:
# `'...\r\{\}'` adds literal backslashes and braces for 90 raw entries
# against its own 86-wide embedding (parameters.py:192); the true TFF
# vocab is these 86 characters, unknown chars map to id 0.
_SHAKESPEARE_CHARS = (
    "dhlptx@DHLPTX $(,048cgkoswCGKOSW[_#'/37;?bfjnrvzBFJNRVZ\"&*.26:"
    "\naeimquyAEIMQUY]!%)-159\r"
)


def shakespeare_vocab():
    """char -> id mapping over the 86-char TFF vocabulary."""
    return {c: i for i, c in enumerate(_SHAKESPEARE_CHARS)}


def load_shakespeare(data_dir: str, seq_len: int = 50,
                     download: bool = False) -> DatasetSplits:
    """TFF shakespeare HDF5 -> per-client char windows with next-char
    targets (ref: federated_datasets.py:309-479, targets at :366-368)."""
    import h5py
    base = os.path.join(data_dir, "shakespeare")
    train_p = os.path.join(base, "shakespeare_train.h5")
    if not os.path.exists(train_p):
        if download:
            archive = os.path.join(base, os.path.basename(URLS["shakespeare"]))
            _fetch(URLS["shakespeare"], archive)
            with tarfile.open(archive, "r:bz2") as tf:
                tf.extractall(base)
        else:
            raise _missing("shakespeare", train_p)

    vocab = shakespeare_vocab()

    def encode(snippets):
        text = b"".join(np.asarray(snippets).tolist()).decode(
            "utf-8", errors="ignore")
        ids = np.asarray([vocab.get(c, 0) for c in text], np.int32)
        n_win = (len(ids) - 1) // seq_len
        if n_win == 0:
            return None, None
        x = ids[:n_win * seq_len].reshape(n_win, seq_len)
        y = ids[1:n_win * seq_len + 1].reshape(n_win, seq_len)
        return x, y

    xs, ys, parts = [], [], []
    offset = 0
    with h5py.File(train_p, "r") as f:
        ex = f["examples"]
        for client in sorted(ex.keys()):
            x, y = encode(ex[client]["snippets"])
            if x is None:
                continue
            xs.append(x)
            ys.append(y)
            parts.append(np.arange(offset, offset + len(x)))
            offset += len(x)
    train_x = np.concatenate(xs)
    train_y = np.concatenate(ys)
    return DatasetSplits(train_x, train_y, train_x[:1], train_y[:1],
                         client_partitions=parts)


# -- token rows --------------------------------------------------------------

def load_tokens(data_dir: str) -> DatasetSplits:
    """Fixed-length rows of token ids, one client's after another's:
    ``<data_dir>/tokens/train.npz`` with ``x`` (int32 ``[rows,
    seq_len]``) and ``client`` (the client each row belongs to, sorted),
    and ``test.npz`` with ``x``. ``y`` is one number a row (the row's
    client; 0 for test rows) and takes no part in the loss: a token
    model makes its next-token target from ``x``
    (models/hybrid_lm.py). No packing, segment ids or document masks."""
    base = os.path.join(data_dir, "tokens")
    train_p = os.path.join(base, "train.npz")
    if not os.path.exists(train_p):
        raise _missing("tokens", train_p)
    with np.load(train_p) as f:
        x = np.asarray(f["x"], np.int32)
        client = np.asarray(f["client"], np.int32)
    if np.any(np.diff(client) < 0):
        raise ValueError(f"{train_p}: rows must be sorted by client")
    with np.load(os.path.join(base, "test.npz")) as f:
        test_x = np.asarray(f["x"], np.int32)
    bounds = np.searchsorted(client, np.arange(int(client.max()) + 2))
    parts = [np.arange(bounds[i], bounds[i + 1])
             for i in range(len(bounds) - 1)]
    return DatasetSplits(x, client, test_x,
                         np.zeros(len(test_x), np.int32),
                         client_partitions=parts)


# -- LibSVM datasets --------------------------------------------------------

_LIBSVM_FILES = {
    "epsilon": ("epsilon_normalized", "epsilon_normalized.t"),
    "rcv1": ("rcv1_train.binary", "rcv1_test.binary"),
    "higgs": ("HIGGS", None),
    "MSD": ("YearPredictionMSD", "YearPredictionMSD.t"),
}


def _read_file_bytes(path: str) -> bytearray:
    """Whole file as ONE mutable bytearray. ``.bz2`` is decompressed
    incrementally (epsilon is ~12 GB of text — never hold the
    compressed and decompressed copies at once); plain files are read
    straight into the output buffer with no intermediate bytes copy."""
    if path.endswith(".bz2"):
        import bz2
        out = bytearray()
        dec = bz2.BZ2Decompressor()
        with open(path, "rb") as f:
            while True:
                data = f.read(1 << 24)
                if not data:
                    break
                while data:
                    if dec.eof:
                        # concatenated bz2 streams (pbzip2/lbzip2;
                        # bz2.decompress parity) — a stream may end at
                        # a chunk boundary, so a fresh decompressor is
                        # started whenever bytes follow an EOF
                        dec = bz2.BZ2Decompressor()
                    out += dec.decompress(data)
                    data = dec.unused_data if dec.eof else b""
        if not dec.eof:
            # bz2.decompress parity: a truncated archive must fail
            # loudly — including one cut inside its FIRST block (no
            # output at all) and the 0-byte file (a valid bz2 stream
            # is never empty) — not yield a silently shortened or
            # empty dataset
            raise ValueError(
                f"{path}: compressed data ended before the "
                "end-of-stream marker was reached")
        return out
    size = os.path.getsize(path)
    buf = bytearray(size)
    view = memoryview(buf)
    filled = 0
    with open(path, "rb", buffering=0) as f:
        # one readinto can short-read (Linux caps a single read(2) at
        # ~2 GiB — epsilon is ~12 GB); loop until the buffer is full
        while filled < size:
            n = f.readinto(view[filled:])
            if not n:
                raise OSError(f"{path}: file shrank while reading "
                              f"({filled}/{size} bytes)")
            filled += n
    return buf


def _read_svmlight_dense(path: str, n_features=None):
    """One svmlight file -> (dense f32 [n, f], labels). Native
    multithreaded parser (native/pipeline.cpp:ft_svmlight_parse) when
    available — epsilon is a ~12 GB text file, and parsing is the load
    bottleneck — sklearn otherwise. Both paths parse the same decimal
    strings to nearest-float, so results are identical. The native
    parser is a pure accelerator: input it rejects (non-ascending or
    duplicate indices, unusual separators) falls through to sklearn
    rather than becoming a new failure mode."""
    from fedtorch_tpu.native.host_pipeline import (
        native_available, parse_svmlight,
    )
    if native_available():
        try:
            parsed = parse_svmlight(_read_file_bytes(path),
                                    n_features=n_features)
            if parsed is not None:
                return parsed
        # ValueError: parser rejected the text; OSError/EOFError: a
        # corrupt or trailing-garbage .bz2 — in every case sklearn
        # gets its own chance at the file
        except (ValueError, OSError, EOFError) as e:
            import sys
            print(f"warning: native svmlight parser rejected {path} "
                  f"({e}); falling back to sklearn", file=sys.stderr)
    # fallback streams from the path (sklearn decompresses .bz2
    # itself) — no whole-file bytes copy on the degraded path
    from sklearn.datasets import load_svmlight_file
    x, y = load_svmlight_file(path, n_features=n_features)
    return np.asarray(x.todense(), np.float32), y


def load_libsvm(dataset: str, data_dir: str,
                download: bool = False) -> DatasetSplits:
    """svmlight parse + standardize for MSD
    (ref: loader/libsvm_datasets.py:26-146)."""
    train_name, test_name = _LIBSVM_FILES[dataset]
    base = os.path.join(data_dir, dataset)

    def find(stem):
        if stem is None:
            return None
        for suffix in ("", ".bz2"):
            p = os.path.join(base, stem + suffix)
            if os.path.exists(p):
                return p
        raise _missing(dataset, os.path.join(base, stem))

    x, y = _read_svmlight_dense(find(train_name))
    te = find(test_name) if test_name else None
    if te:
        tx, ty = _read_svmlight_dense(te, n_features=x.shape[1])
    else:
        tx, ty = x[-1000:], y[-1000:]
        x, y = x[:-1000], y[:-1000]
    if dataset == "MSD":
        mu, sd = x.mean(0), x.std(0) + 1e-8
        x, tx = (x - mu) / sd, (tx - mu) / sd
        y = y.astype(np.float32)
        ty = ty.astype(np.float32)
    else:
        # binary labels in {-1, +1} or {0, 1} -> {0, 1}
        y = (np.asarray(y) > 0).astype(np.int64)
        ty = (np.asarray(ty) > 0).astype(np.int64)
    return DatasetSplits(x, y, tx, ty)


# -- Adult ------------------------------------------------------------------

_ADULT_COLUMNS = ["age", "workclass", "fnlwgt", "education", "education-num",
                  "marital-status", "occupation", "relationship", "race",
                  "sex", "capital-gain", "capital-loss", "hours-per-week",
                  "native-country", "income"]


def load_adult(data_dir: str, sensitive_feature: int = 9,
               download: bool = False) -> DatasetSplits:
    """UCI adult CSV: categorical encoding + standardization + sensitive
    feature metadata (ref: loader/adult_loader.py:28-160; default
    sensitive feature 9 = sex, parameters.py:37)."""
    import pandas as pd
    from sklearn.preprocessing import StandardScaler
    base = os.path.join(data_dir, "adult")
    train_p = os.path.join(base, "adult.data")
    test_p = os.path.join(base, "adult.test")
    for p, name in ((train_p, "adult.data"), (test_p, "adult.test")):
        if not os.path.exists(p):
            if download:
                _fetch(URLS["adult"] + name, p)
            else:
                raise _missing("adult", p)

    def read(path, skip=0):
        return pd.read_csv(path, names=_ADULT_COLUMNS, skiprows=skip,
                           skipinitialspace=True, na_values="?").dropna()

    # Encode categoricals over the CONCATENATED frames so train/test share
    # codes (a category present in only one file would otherwise shift the
    # integer coding; the reference does the same, adult_loader.py:90-110).
    df_train, df_test = read(train_p), read(test_p, skip=1)
    df = pd.concat([df_train, df_test], keys=["train", "test"])
    y_all = df["income"].str.contains(">50K").astype(np.int64)
    df = df.drop(columns=["income"])
    for col in df.columns:
        if not pd.api.types.is_numeric_dtype(df[col]):
            df[col] = df[col].astype("category").cat.codes
    train_x = df.loc["train"].to_numpy(np.float32)
    test_x = df.loc["test"].to_numpy(np.float32)
    train_y = y_all.loc["train"].to_numpy()
    test_y = y_all.loc["test"].to_numpy()
    sensitive = train_x[:, sensitive_feature].copy()
    scaler = StandardScaler().fit(train_x)
    return DatasetSplits(scaler.transform(train_x).astype(np.float32),
                         train_y,
                         scaler.transform(test_x).astype(np.float32),
                         test_y, sensitive_values=sensitive)


# -- STL10 ------------------------------------------------------------------

def load_stl10(data_dir: str, download: bool = False) -> DatasetSplits:
    base = os.path.join(data_dir, "stl10_binary")
    paths = {k: os.path.join(base, k + ".bin")
             for k in ("train_X", "train_y", "test_X", "test_y")}
    for p in paths.values():
        if not os.path.exists(p):
            raise _missing("stl10", p)

    def rx(p):
        x = np.fromfile(p, dtype=np.uint8).reshape(-1, 3, 96, 96)
        return (x.transpose(0, 3, 2, 1).astype(np.float32) / 255.0 - 0.5) / 0.5

    def ry(p):
        return np.fromfile(p, dtype=np.uint8).astype(np.int64) - 1

    return DatasetSplits(rx(paths["train_X"]), ry(paths["train_y"]),
                         rx(paths["test_X"]), ry(paths["test_y"]))


# -- Factory ----------------------------------------------------------------

def get_dataset(cfg: DataConfig, num_clients: int,
                download: bool = False, seq_len: int = 50) -> DatasetSplits:
    """Dispatch on dataset name (prepare_data.py:124-163)."""
    name, root = cfg.dataset, cfg.data_dir
    if name == "synthetic":
        # synthetic_samples_per_client scales the reference's 500/1000
        # lognormal size window (federated_datasets.py:253 defaults)
        # proportionally: min = the knob, max = 2x — the default 500
        # reproduces the reference exactly
        spc = cfg.synthetic_samples_per_client
        data = generate_synthetic(
            num_tasks=num_clients, alpha=cfg.synthetic_alpha,
            beta=cfg.synthetic_beta, num_dim=cfg.synthetic_dim,
            num_classes=cfg.synthetic_num_classes,
            regression=cfg.synthetic_regression,
            min_num_samples=spc, max_num_samples=2 * spc)
        sizes = [len(y) for y in data.client_y]
        offsets = np.concatenate([[0], np.cumsum(sizes)])
        parts = [np.arange(offsets[i], offsets[i + 1])
                 for i in range(num_clients)]
        return DatasetSplits(
            train_x=np.concatenate(data.client_x),
            train_y=np.concatenate(data.client_y),
            test_x=data.test_x, test_y=data.test_y,
            client_partitions=parts)
    if name in ("mnist", "fashion_mnist"):
        return load_mnist_family(name, root, download)
    if name in ("cifar10", "cifar100"):
        return load_cifar(name, root, download)
    if name in ("emnist", "emnist_full"):
        return load_emnist(root, full=(name == "emnist_full"),
                           download=download,
                           allow_train_as_test=cfg.allow_train_as_test)
    if name == "shakespeare":
        return load_shakespeare(root, seq_len=seq_len, download=download)
    if name == "tokens":
        return load_tokens(root)
    if name in _LIBSVM_FILES:
        return load_libsvm(name, root, download)
    if name == "adult":
        return load_adult(root, cfg.sensitive_feature, download)
    if name == "stl10":
        return load_stl10(root, download)
    raise ValueError(f"Unknown dataset {name!r}")
