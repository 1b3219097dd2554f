"""The million-client data plane (ISSUE 18): the ClientStore seam
(zero-copy RAM store, manifest-described mmap store, chunked writer),
O(k) 'sparse' participation (device draw + host RoundSchedule replay +
async event scheduler) and the config/CLI surface for the new knobs."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedtorch_tpu.algorithms import make_algorithm
from fedtorch_tpu.async_plane.scheduler import AsyncSchedule
from fedtorch_tpu.config import (
    DataConfig, ExperimentConfig, FederatedConfig, ModelConfig,
    OptimConfig, TrainConfig,
)
from fedtorch_tpu.data import build_federated_data
from fedtorch_tpu.data.batching import ClientData
from fedtorch_tpu.data.streaming import (
    MANIFEST_NAME, HostClientStore, MmapClientStore, MmapStoreWriter,
    save_client_store,
)
from fedtorch_tpu.models import define_model
from fedtorch_tpu.parallel import FederatedTrainer
from fedtorch_tpu.parallel.federated import participation_indices
from fedtorch_tpu.robustness import HostSeamError


def make_cfg(plane="stream", store="ram", store_dir="",
             participation_mode="perm", num_clients=8, online_rate=0.5):
    return ExperimentConfig(
        data=DataConfig(dataset="synthetic", synthetic_dim=20,
                        batch_size=16, synthetic_alpha=0.5,
                        synthetic_beta=0.5, data_plane=plane,
                        store=store, store_dir=store_dir),
        federated=FederatedConfig(
            federated=True, num_clients=num_clients,
            online_client_rate=online_rate, algorithm="fedavg",
            sync_type="local_step",
            participation_mode=participation_mode),
        model=ModelConfig(arch="logistic_regression"),
        optim=OptimConfig(lr=0.3, weight_decay=0.0),
        train=TrainConfig(local_step=3),
    ).finalize()


def build(cfg, data):
    model = define_model(cfg, batch_size=cfg.data.batch_size)
    return FederatedTrainer(cfg, model, make_algorithm(cfg), data.train)


def _toy_population(C=6, n_max=10, F=4, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(C, n_max, F).astype(np.float32)
    y = rng.randint(0, 10, (C, n_max)).astype(np.int32)
    sizes = rng.randint(0, n_max + 1, C).astype(np.int32)
    sizes[0], sizes[1] = n_max, 0  # a full shard and an empty client
    return ClientData(x=x, y=y, sizes=sizes)


def assert_feeds_equal(a, b):
    for la, lb in zip(a, b):
        assert (la is None) == (lb is None)
        if la is not None:
            np.testing.assert_array_equal(np.asarray(la),
                                          np.asarray(lb))


# -- the RAM store: zero-copy + the int32-overflow fallback ------------------
def test_host_store_zero_copy_when_contiguous():
    """The no-silent-duplication contract: contiguous host inputs are
    ALIASED, not copied — at million-client scale an accidental copy
    doubles peak host RAM."""
    data = _toy_population()
    store = HostClientStore(data)
    assert np.shares_memory(store.x, data.x)
    assert np.shares_memory(store.y, data.y)
    assert np.shares_memory(store.sizes, data.sizes)
    # a non-contiguous input pays exactly one materialization
    sliced = HostClientStore(ClientData(
        x=data.x[:, ::2], y=data.y[:, ::2], sizes=data.sizes))
    assert not np.shares_memory(sliced.x, data.x)
    assert sliced.x.flags.c_contiguous


def test_int32_overflow_fallback_bitwise():
    """Stores past 2^31-1 total rows clear ``_native_ok`` and gather
    via numpy fancy indexing; forcing the flag off must not change a
    single byte of ``pack`` or ``pack_window`` output (including the
    clamped ``pre_round`` columns when batch_size > n_max)."""
    store = HostClientStore(_toy_population())
    assert store._native_ok
    idx = np.asarray([1, 3, 0], np.int64)
    rows = np.random.RandomState(2).randint(
        0, store.n_max, (3, 5)).astype(np.int64)
    over = store.n_max + 3  # forces the pre-column clamp
    native = store.pack(idx, rows, batch_size=over)
    idxs = np.asarray([[0, 1], [2, 3]], np.int64)
    rowss = np.random.RandomState(3).randint(
        0, store.n_max, (2, 2, 4)).astype(np.int64)
    native_w = store.pack_window(idxs, rowss, batch_size=over)

    store._native_ok = False  # what a past-2^31-rows store sets
    assert_feeds_equal(store.pack(idx, rows, batch_size=over), native)
    assert_feeds_equal(store.pack_window(idxs, rowss, batch_size=over),
                       native_w)


# -- the mmap store: round-trip + feed parity --------------------------------
@pytest.mark.parametrize("cps,chunk", [(2, 2), (3, 2), (64, 4096)])
def test_mmap_store_matches_ram_store_bitwise(tmp_path, cps, chunk):
    """Same schedule => identical RoundFeed bytes from the disk-backed
    store and the RAM store, across shard-straddling chunked writes
    (cps=2/3) and the single-shard layout (cps=64). Residency splits
    as documented: the mmap store pins only the sizes vector."""
    data = _toy_population()
    ram = HostClientStore(data)
    save_client_store(str(tmp_path), data, clients_per_shard=cps,
                      chunk_clients=chunk)
    mm = MmapClientStore(str(tmp_path))
    assert (mm.num_clients, mm.n_max) == (ram.num_clients, ram.n_max)
    np.testing.assert_array_equal(mm.sizes, ram.sizes)

    idx = np.asarray([5, 1, 0, 3], np.int64)
    rows = np.random.RandomState(1).randint(
        0, mm.n_max, (4, 6)).astype(np.int64)
    assert_feeds_equal(mm.pack(idx, rows, 4), ram.pack(idx, rows, 4))
    assert_feeds_equal(mm.pack_shards(idx, 4), ram.pack_shards(idx, 4))
    idxs, rowss = idx.reshape(2, 2), rows.reshape(2, 2, 6)
    assert_feeds_equal(mm.pack_window(idxs, rowss, 4),
                       ram.pack_window(idxs, rowss, 4))
    for a, b in zip(mm.pack_probe(idx[:2], rows[:2, :3]),
                    ram.pack_probe(idx[:2], rows[:2, :3])):
        np.testing.assert_array_equal(a, b)

    # residency: RAM store holds the arrays; mmap store maps them
    assert ram.resident_nbytes == data.x.nbytes + data.y.nbytes
    assert ram.mapped_nbytes == 0
    assert mm.resident_nbytes == mm.sizes.nbytes
    assert mm.mapped_nbytes == data.x.nbytes + data.y.nbytes


def test_mmap_as_client_data_is_zero_ram_view(tmp_path):
    """The trainer-construction view: real sizes, stride-0 broadcast
    stubs for x/y (shape/dtype metadata only — never O(C) RAM)."""
    data = _toy_population()
    save_client_store(str(tmp_path), data)
    view = MmapClientStore(str(tmp_path)).as_client_data()
    assert view.x.shape == data.x.shape
    assert view.x.dtype == data.x.dtype
    assert view.y.shape == data.y.shape
    assert view.x.strides == (0,) * view.x.ndim
    np.testing.assert_array_equal(view.sizes, data.sizes)


def test_store_manifest_validation(tmp_path):
    with pytest.raises(ValueError, match="save_client_store"):
        MmapClientStore(str(tmp_path))  # no manifest yet

    data = _toy_population()
    mpath = save_client_store(str(tmp_path), data, clients_per_shard=2)
    man = json.loads(mpath.read_text())

    def rewrite(**kw):
        mpath.write_text(json.dumps({**man, **kw}))

    rewrite(format="not-a-store")
    with pytest.raises(ValueError, match="format"):
        MmapClientStore(str(tmp_path))
    rewrite(version=99)
    with pytest.raises(ValueError, match="version"):
        MmapClientStore(str(tmp_path))
    # per-shard gather must stay int32-legal by construction
    rewrite(clients_per_shard=2 ** 28, n_max=2 ** 10)
    with pytest.raises(ValueError, match="int32"):
        MmapClientStore(str(tmp_path))
    # shard list out of step with the layout
    bad = json.loads(json.dumps(man))
    bad["tensors"]["x"]["shards"] = bad["tensors"]["x"]["shards"][:-1]
    mpath.write_text(json.dumps(bad))
    with pytest.raises(ValueError, match="shards"):
        MmapClientStore(str(tmp_path))
    # sizes file out of step with num_clients
    mpath.write_text(json.dumps(man))
    sizes_path = tmp_path / man["sizes_file"]
    sizes_path.write_bytes(sizes_path.read_bytes()[:-4])
    with pytest.raises(ValueError, match="sizes"):
        MmapClientStore(str(tmp_path))


def test_store_writer_guards():
    with pytest.raises(ValueError, match="int32"):
        MmapStoreWriter("/tmp/unused", n_max=2 ** 20,
                        x_feat=(1,), y_feat=(), x_dtype=np.float32,
                        y_dtype=np.int32, clients_per_shard=2 ** 12)


def test_store_writer_rejects_mismatched_chunks(tmp_path):
    w = MmapStoreWriter(str(tmp_path), n_max=4, x_feat=(2,), y_feat=(),
                        x_dtype=np.float32, y_dtype=np.int32)
    with pytest.raises(ValueError, match="chunk shapes"):
        w.append(np.zeros((3, 4, 2), np.float32),
                 np.zeros((3, 5), np.int32), np.zeros((3,), np.int32))


# -- the mmap store through the trainer --------------------------------------
def test_mmap_trainer_matches_ram_trainer_bitwise(tmp_path):
    """data.store='mmap' vs the default RAM store: BITWISE-identical
    trajectories — the store seam changes residency, never bytes."""
    cfg_ram = make_cfg()
    data = build_federated_data(cfg_ram)
    save_client_store(str(tmp_path), data.train, clients_per_shard=3)
    cfg_mm = make_cfg(store="mmap", store_dir=str(tmp_path))
    t_ram, t_mm = build(cfg_ram, data), build(cfg_mm, data)
    assert t_mm.host_store.resident_nbytes \
        < t_ram.host_store.resident_nbytes
    s1, c1 = t_ram.init_state(jax.random.key(0))
    s2, c2 = t_mm.init_state(jax.random.key(0))
    for _ in range(3):
        s1, c1, m1 = t_ram.run_round(s1, c1)
        s2, c2, m2 = t_mm.run_round(s2, c2)
    for la, lb in zip(jax.tree.leaves((s1.params, s1.aux, c1, m1)),
                      jax.tree.leaves((s2.params, s2.aux, c2, m2))):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    t_ram.invalidate_stream()
    t_mm.invalidate_stream()


def test_torn_shard_raises_named_seam_error(tmp_path):
    """A truncated shard file must surface as a NAMED HostSeamError
    chain — the mmap length check fails the 'stream.gather' bounded
    retry, the trainer's producer-rebuild layer retries against the
    same torn file and escalates as 'stream.producer' chained to the
    gather-seam exhaustion — never as a raw mmap ValueError from a
    worker thread."""
    cfg = make_cfg(store="mmap", store_dir=str(tmp_path))
    data = build_federated_data(cfg)
    save_client_store(str(tmp_path), data.train, clients_per_shard=3)
    for shard in tmp_path.glob("x.*.bin"):  # tear every x shard
        shard.write_bytes(shard.read_bytes()[:16])
    t = build(cfg, data)
    server, clients = t.init_state(jax.random.key(0))
    try:
        with pytest.raises(HostSeamError, match="stream.gather") as ei:
            for _ in range(3):
                server, clients, _ = t.run_round(server, clients)
        assert ei.value.seam == "stream.producer"
        cause = ei.value.__cause__
        assert isinstance(cause, HostSeamError)
        assert cause.seam == "stream.gather"
    finally:
        t.invalidate_stream()


def test_trainer_rejects_store_shape_mismatch(tmp_path):
    cfg = make_cfg(store="mmap", store_dir=str(tmp_path),
                   num_clients=8)
    data = build_federated_data(cfg)
    save_client_store(str(tmp_path), _toy_population(C=5))
    with pytest.raises(ValueError, match="mmap client store"):
        build(cfg, data)


# -- O(k) 'sparse' participation ---------------------------------------------
def test_sparse_draw_valid_and_forces_client0():
    key = jax.random.key(11)
    for r in (0, 1, 7):
        idx = np.asarray(participation_indices(
            jax.random.fold_in(key, r), 1000, 16, jnp.int32(r),
            mode="sparse"))
        assert len(set(idx.tolist())) == 16  # without replacement
        assert (idx >= 0).all() and (idx < 1000).all()
        if r == 0:
            assert 0 in idx  # round-0 forcing, same as 'perm'


def test_perm_mode_is_the_untouched_default():
    key = jax.random.key(5)
    legacy = participation_indices(key, 40, 8, jnp.int32(3))
    np.testing.assert_array_equal(
        np.asarray(legacy),
        np.asarray(participation_indices(key, 40, 8, jnp.int32(3),
                                         mode="perm")))
    # and it IS the legacy permutation prefix, bitwise
    np.testing.assert_array_equal(
        np.asarray(legacy),
        np.asarray(jax.random.permutation(key, 40)[:8]))


def test_sparse_stream_matches_device_bitwise():
    """participation_mode='sparse' replays bit-exactly through the
    host RoundSchedule: the stream plane's trajectory equals the
    device plane's over multiple rounds."""
    cfg_d = make_cfg(plane="device", participation_mode="sparse")
    cfg_s = make_cfg(plane="stream", participation_mode="sparse")
    data = build_federated_data(cfg_d)
    t_dev, t_str = build(cfg_d, data), build(cfg_s, data)
    s1, c1 = t_dev.init_state(jax.random.key(9))
    s2, c2 = t_str.init_state(jax.random.key(9))
    for _ in range(3):
        s1, c1, m1 = t_dev.run_round(s1, c1)
        s2, c2, m2 = t_str.run_round(s2, c2)
    for la, lb in zip(jax.tree.leaves((s1.params, s1.aux, c1, m1)),
                      jax.tree.leaves((s2.params, s2.aux, c2, m2))):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    t_str.invalidate_stream()


# -- the async event scheduler's sparse mode ---------------------------------
def _sched(start_commit=0, num_clients=16, **kw):
    key = jax.random.key(7)
    key_data = np.asarray(jax.device_get(jax.random.key_data(key)))
    return AsyncSchedule(
        key_data, jax.random.key_impl(key), num_clients=num_clients,
        concurrency=6, buffer_size=3, ring_size=8,
        start_commit=start_commit, straggler_rate=0.4,
        straggler_step_frac=0.1, **kw)


def test_async_perm_default_bitwise_unchanged():
    """participation_mode defaults to 'perm' and the explicit spelling
    is byte-identical — the legacy async stream is pinned."""
    a, b = _sched(), _sched(participation_mode="perm")
    for _ in range(5):
        pa, pb = a.next_commit(), b.next_commit()
        assert pa.commit == pb.commit
        np.testing.assert_array_equal(pa.idx, pb.idx)
        np.testing.assert_array_equal(pa.version, pb.version)
        np.testing.assert_array_equal(pa.arrival_times,
                                      pb.arrival_times)


def test_async_sparse_deterministic_and_valid():
    a, b = _sched(participation_mode="sparse"), \
        _sched(participation_mode="sparse")
    for _ in range(6):
        pa, pb = a.next_commit(), b.next_commit()
        np.testing.assert_array_equal(pa.idx, pb.idx)
        np.testing.assert_array_equal(pa.arrival_times,
                                      pb.arrival_times)
        # in-flight cohort stays distinct clients in range
        assert len(set(pa.idx.tolist())) == len(pa.idx)
        assert (pa.idx >= 0).all() and (pa.idx < 16).all()


def test_async_sparse_fast_forward_equals_stepped():
    live = _sched(participation_mode="sparse")
    for _ in range(4):
        live.next_commit()
    resumed = _sched(start_commit=4, participation_mode="sparse")
    for _ in range(3):
        pl, pr = live.next_commit(), resumed.next_commit()
        assert pl.commit == pr.commit
        np.testing.assert_array_equal(pl.idx, pr.idx)
        np.testing.assert_array_equal(pl.version, pr.version)


def test_async_rejects_unknown_mode():
    with pytest.raises(ValueError, match="participation_mode"):
        _sched(participation_mode="reservoir")


# -- config / CLI surface ----------------------------------------------------
def test_config_rejects_bad_store_knobs():
    with pytest.raises(ValueError, match="data.store"):
        make_cfg(store="redis")
    with pytest.raises(ValueError, match="stream-plane client store"):
        make_cfg(plane="device", store="mmap", store_dir="/x")
    with pytest.raises(ValueError, match="needs data.store_dir"):
        make_cfg(store="mmap")
    with pytest.raises(ValueError, match="participation_mode"):
        make_cfg(participation_mode="reservoir")


def test_cli_flags_map_to_config(tmp_path):
    from fedtorch_tpu.cli import args_to_config, build_parser
    cfg = args_to_config(build_parser().parse_args(
        ["--federated", "true", "-d", "synthetic",
         "--data_plane", "stream", "--data_store", "mmap",
         "--data_store_dir", str(tmp_path),
         "--participation_mode", "sparse"]))
    assert cfg.data.store == "mmap"
    assert cfg.data.store_dir == str(tmp_path)
    assert cfg.federated.participation_mode == "sparse"
