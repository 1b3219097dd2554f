"""Checkpoint lifecycle satellites (ISSUE 4): bounded retention
(``checkpoint.keep_last_n``), the pinned ``run_dir``, and resume edge
cases — a corrupt ``checkpoint.json`` beside a valid per-round keep,
and the heavily-padded template graft (``num_clients`` < device
count, the mesh-shape-independence contract the degraded-pod resume
rides on). ISSUE 28: a save's payload goes to disk once —
``model_best.ckpt`` is a hard link to the ``checkpoint.ckpt`` just
written, the frame is written in two parts, the keeps stay files of
their own. ISSUE 30: the payload is never built — a list of pieces that
borrow the snapshot's arrays, byte for byte what flax ``to_bytes``
gives, hashed and written piece by piece."""
import errno
import json
import os

import jax
import numpy as np
import pytest
from flax import serialization

from fedtorch_tpu.algorithms import make_algorithm
from fedtorch_tpu.config import (
    CheckpointConfig, DataConfig, ExperimentConfig, FederatedConfig,
    ModelConfig, OptimConfig, TrainConfig,
)
from fedtorch_tpu.data import build_federated_data
from fedtorch_tpu.models import define_model
from fedtorch_tpu.parallel import FederatedTrainer
from fedtorch_tpu.utils import (
    init_checkpoint_dir, maybe_resume, save_checkpoint,
)
from fedtorch_tpu.utils import checkpoint as ckpt_mod
from fedtorch_tpu.utils.checkpoint import collect_round_keeps


def make_experiment(num_clients=6, ckpt_kw=None):
    cfg = ExperimentConfig(
        data=DataConfig(dataset="synthetic", synthetic_dim=10,
                        batch_size=8),
        federated=FederatedConfig(
            federated=True, num_clients=num_clients, num_comms=4,
            online_client_rate=0.5, algorithm="fedavg",
            sync_type="local_step"),
        model=ModelConfig(arch="logistic_regression"),
        optim=OptimConfig(lr=0.1, weight_decay=0.0),
        train=TrainConfig(local_step=2),
        checkpoint=CheckpointConfig(**(ckpt_kw or {})),
    ).finalize()
    data = build_federated_data(cfg)
    model = define_model(cfg, batch_size=cfg.data.batch_size)
    trainer = FederatedTrainer(cfg, model, make_algorithm(cfg),
                               data.train)
    server, clients = trainer.init_state(jax.random.key(0))
    return cfg, trainer, server, clients


def _round_keeps(d):
    return sorted(f for f in os.listdir(d)
                  if f.startswith("checkpoint_r"))


# -- bounded retention -------------------------------------------------------
class TestKeepLastN:
    def test_gc_keeps_newest_n(self, tmp_path):
        d = str(tmp_path)
        cfg, trainer, server, clients = make_experiment(
            ckpt_kw={"keep_last_n": 2})
        for _ in range(5):
            server, clients, _ = trainer.run_round(server, clients)
            save_checkpoint(d, server, clients, cfg, 0.0, False,
                            save_all=True)
        assert _round_keeps(d) == ["checkpoint_r4.ckpt",
                                   "checkpoint_r5.ckpt"]
        # checkpoint.ckpt itself is never a GC candidate
        assert os.path.exists(os.path.join(d, "checkpoint.ckpt"))

    def test_default_unlimited_preserves_save_all(self, tmp_path):
        d = str(tmp_path)
        cfg, trainer, server, clients = make_experiment()
        assert cfg.checkpoint.keep_last_n == 0
        for _ in range(4):
            server, clients, _ = trainer.run_round(server, clients)
            save_checkpoint(d, server, clients, cfg, 0.0, False,
                            save_all=True)
        assert len(_round_keeps(d)) == 4  # save_all semantics intact

    def test_model_best_never_collected(self, tmp_path):
        d = str(tmp_path)
        cfg, trainer, server, clients = make_experiment(
            ckpt_kw={"keep_last_n": 1})
        for i in range(3):
            server, clients, _ = trainer.run_round(server, clients)
            save_checkpoint(d, server, clients, cfg, 0.5, is_best=True,
                            save_all=True)
        assert _round_keeps(d) == ["checkpoint_r3.ckpt"]
        assert os.path.exists(os.path.join(d, "model_best.ckpt"))
        assert os.path.exists(os.path.join(d, "model_best.json"))

    def test_collect_round_keeps_sorts_numerically(self, tmp_path):
        d = str(tmp_path)
        # r10 must outrank r9 (lexical order would GC it); content is
        # legacy-unframed-shaped — a sub-magic-length stub would count
        # as a torn frame and be swept regardless of retention
        for r in (2, 9, 10):
            with open(os.path.join(d, f"checkpoint_r{r}.ckpt"),
                      "wb") as f:
                f.write(b"legacy-unframed-checkpoint-bytes")
        removed = collect_round_keeps(d, 2)
        assert [os.path.basename(p) for p in removed] == \
            ["checkpoint_r2.ckpt"]
        assert _round_keeps(d) == ["checkpoint_r10.ckpt",
                                   "checkpoint_r9.ckpt"]

    def test_resumed_run_gc_spans_earlier_attempts(self, tmp_path):
        """Retention is directory-wide, not per-process: keeps written
        by the pre-restart attempt are collected by the resumed one."""
        d = str(tmp_path)
        cfg, trainer, server, clients = make_experiment(
            ckpt_kw={"keep_last_n": 2})
        for _ in range(2):  # "first attempt": rounds 1-2
            server, clients, _ = trainer.run_round(server, clients)
            save_checkpoint(d, server, clients, cfg, 0.0, False,
                            save_all=True)
        for _ in range(2):  # "restarted attempt": rounds 3-4
            server, clients, _ = trainer.run_round(server, clients)
            save_checkpoint(d, server, clients, cfg, 0.0, False,
                            save_all=True)
        assert _round_keeps(d) == ["checkpoint_r3.ckpt",
                                   "checkpoint_r4.ckpt"]


# -- run_dir -----------------------------------------------------------------
class TestRunDir:
    def test_run_dir_used_exactly(self, tmp_path):
        d = str(tmp_path / "stable")
        cfg, *_ = make_experiment(ckpt_kw={"run_dir": d})
        assert init_checkpoint_dir(cfg) == d
        assert os.path.isdir(d)

    def test_default_keeps_hyperparam_layout(self, tmp_path):
        cfg, *_ = make_experiment(
            ckpt_kw={"checkpoint_dir": str(tmp_path)})
        path = init_checkpoint_dir(cfg)
        # <root>/<dataset>/<arch>/<hyperparam folder>
        assert path.startswith(
            os.path.join(str(tmp_path), "synthetic",
                         "logistic_regression"))


# -- resume edge cases -------------------------------------------------------
class TestResumeEdgeCases:
    def test_corrupt_meta_beside_valid_keep_skips_cleanly(
            self, tmp_path):
        """checkpoint_index resume reads checkpoint.json for compat:
        undecodable meta beside a perfectly valid per-round .ckpt must
        skip resume with a warning, not die on a JSON traceback."""
        d = str(tmp_path)
        cfg, trainer, server, clients = make_experiment()
        server, clients, _ = trainer.run_round(server, clients)
        save_checkpoint(d, server, clients, cfg, 0.0, False,
                        save_all=True)
        assert os.path.exists(os.path.join(d, "checkpoint_r1.ckpt"))
        with open(os.path.join(d, "checkpoint.json"), "w") as f:
            f.write('{"arguments": {truncated')
        s2, c2 = trainer.init_state(jax.random.key(0))
        with pytest.warns(RuntimeWarning, match="undecodable meta"):
            s3, c3, best, resumed = maybe_resume(d, s2, c2, cfg, "1")
        assert not resumed and best == 0.0
        assert int(jax.device_get(s3.round)) == 0  # fresh state kept

    def test_resume_with_fewer_clients_than_devices(self, tmp_path):
        """num_clients < device count: the 8-device test mesh pads 3
        clients to 8 slots — the checkpoint carries ONLY the 3 real
        clients and the graft must land them in the padded template
        with the trajectory intact (the same contract, at the padding
        extreme, that degraded-pod resume relies on)."""
        d = str(tmp_path)
        C = 3
        cfg, trainer, server, clients = make_experiment(num_clients=C)
        assert trainer.padded_clients >= jax.device_count() > C
        fingerprints = []
        for _ in range(4):
            server, clients, m = trainer.run_round(server, clients)
            jax.block_until_ready(server.params)
            fingerprints.append(repr(float(m.train_loss.sum())))
        # checkpoint at round 2 of a REPLAY from the same seed
        cfg2, tr2, s2, c2 = make_experiment(num_clients=C)
        for _ in range(2):
            s2, c2, _ = tr2.run_round(s2, c2)
        save_checkpoint(d, s2, c2, cfg2, 0.0, False)
        # fresh trainer resumes and must reproduce rounds 3-4 bitwise
        cfg3, tr3, s3, c3 = make_experiment(num_clients=C)
        s3, c3, _, resumed = maybe_resume(d, s3, c3, cfg3, None)
        assert resumed and int(jax.device_get(s3.round)) == 2
        tail = []
        for _ in range(2):
            s3, c3, m = tr3.run_round(s3, c3)
            jax.block_until_ready(s3.params)
            tail.append(repr(float(m.train_loss.sum())))
        assert tail == fingerprints[2:]


# -- one payload write a save ------------------------------------------------
def _read(d, name):
    with open(os.path.join(d, name), "rb") as f:
        return f.read()


def _restored_round(blob, cfg, server, clients):
    """Verify ``blob`` the way resume does (frame, then flax) and hand
    back the round it holds."""
    payload, bad = ckpt_mod._unframe_payload(blob)
    assert bad is None, bad
    template = {"server": ckpt_mod._unkey(server),
                "clients": ckpt_mod._strip_padding(
                    clients, cfg.federated.num_clients)}
    return int(serialization.from_bytes(template, payload)["server"].round)


def _spans(tel):
    """The recorder's spans as (name, start, end, args)."""
    return [(e["name"], e["ts"], e["ts"] + e["dur"], e.get("args") or {})
            for e in tel.spans.to_trace_events() if e.get("ph") == "X"]


@pytest.fixture
def recorder(tmp_path):
    from fedtorch_tpu.telemetry import Telemetry
    tel = Telemetry(str(tmp_path / "telemetry")).install()
    try:
        yield tel
    finally:
        tel.close()


class TestPayloadGoesToDiskOnce:
    def test_best_is_a_link_to_the_latest_and_both_verify(
            self, tmp_path, recorder):
        d = str(tmp_path / "ck")
        cfg, trainer, server, clients = make_experiment()
        server, clients, _ = trainer.run_round(server, clients)
        os.makedirs(d)
        # a crashed writer's leftover must not refuse the link (EEXIST)
        with open(os.path.join(d, "model_best.ckpt.tmp"), "wb") as f:
            f.write(b"stale")
        save_checkpoint(d, server, clients, cfg, 0.5, is_best=True)
        latest, best = (os.path.join(d, n)
                        for n in ("checkpoint.ckpt", "model_best.ckpt"))
        assert os.path.samefile(latest, best)
        assert os.stat(best).st_nlink == 2
        for name in ("checkpoint.ckpt", "model_best.ckpt"):
            assert _restored_round(_read(d, name), cfg, server,
                                   clients) == 1
        assert sorted(os.listdir(d)) == [
            "checkpoint.ckpt", "checkpoint.json", "model_best.ckpt",
            "model_best.json"]          # no .tmp left behind
        assert _read(d, "model_best.json") == _read(d, "checkpoint.json")

        # what the save recorded: one serialization, one payload file,
        # the link; every sub-span inside ``checkpoint.write``
        spans = _spans(recorder)
        (_, w0, w1, wargs), = [s for s in spans
                               if s[0] == "checkpoint.write"]
        assert wargs == {"round": 1}
        inner = [s for s in spans if s[0] in (
            "checkpoint.serialize", "checkpoint.file_write",
            "checkpoint.link")]
        assert all(w0 <= s[1] and s[2] <= w1 for s in inner)
        assert [s[0] for s in inner].count("checkpoint.serialize") == 1
        size = os.path.getsize(latest)
        assert [(s[3]["name"], s[3]["bytes"]) for s in inner
                if s[0] == "checkpoint.file_write"] == [
            ("checkpoint.ckpt", size),
            ("checkpoint.json", len(_read(d, "checkpoint.json"))),
            ("model_best.json", len(_read(d, "model_best.json")))]
        assert [s[3] for s in inner if s[0] == "checkpoint.link"] == [
            {"name": "model_best.ckpt", "fallback": False}]

    def test_later_save_leaves_model_best_its_round_bitwise(
            self, tmp_path):
        """No checkpoint is written in place: the next save renames a
        new inode over ``checkpoint.ckpt`` and ``model_best.ckpt``
        keeps the old one."""
        d = str(tmp_path)
        cfg, trainer, server, clients = make_experiment()
        server, clients, _ = trainer.run_round(server, clients)
        save_checkpoint(d, server, clients, cfg, 0.5, is_best=True)
        best_then = _read(d, "model_best.ckpt")
        inode_then = os.stat(os.path.join(d, "model_best.ckpt")).st_ino
        s2, c2, _ = trainer.run_round(server, clients)
        save_checkpoint(d, s2, c2, cfg, 0.5, is_best=False)
        latest, best = (os.path.join(d, n)
                        for n in ("checkpoint.ckpt", "model_best.ckpt"))
        assert not os.path.samefile(latest, best)
        assert os.stat(best).st_ino == inode_then
        assert os.stat(latest).st_ino != inode_then
        assert os.stat(best).st_nlink == 1
        assert _read(d, "model_best.ckpt") == best_then
        assert _restored_round(best_then, cfg, s2, c2) == 1
        assert _restored_round(_read(d, "checkpoint.ckpt"), cfg, s2,
                               c2) == 2
        # and a later best save moves the name to the new latest
        save_checkpoint(d, s2, c2, cfg, 0.6, is_best=True)
        assert os.path.samefile(latest, best)
        assert _restored_round(_read(d, "model_best.ckpt"), cfg, s2,
                               c2) == 2

    def test_refused_link_falls_back_to_a_written_copy(
            self, tmp_path, monkeypatch, recorder):
        d = str(tmp_path / "ck")
        cfg, trainer, server, clients = make_experiment()
        server, clients, _ = trainer.run_round(server, clients)

        def refuse(src, dst, **kw):
            raise OSError(errno.EPERM, "links not permitted here")

        monkeypatch.setattr(os, "link", refuse)
        save_checkpoint(d, server, clients, cfg, 0.5, is_best=True)
        latest, best = (os.path.join(d, n)
                        for n in ("checkpoint.ckpt", "model_best.ckpt"))
        assert not os.path.samefile(latest, best)
        assert _read(d, "model_best.ckpt") == _read(d, "checkpoint.ckpt")
        assert _restored_round(_read(d, "model_best.ckpt"), cfg, server,
                               clients) == 1
        assert not os.path.exists(best + ".tmp")
        spans = _spans(recorder)
        assert [s[3] for s in spans if s[0] == "checkpoint.link"] == [
            {"name": "model_best.ckpt", "fallback": True}]
        assert [s[3]["name"] for s in spans
                if s[0] == "checkpoint.file_write"] == [
            "checkpoint.ckpt", "checkpoint.json", "model_best.ckpt",
            "model_best.json"]

    @pytest.mark.parametrize("torn", [False, True])
    def test_file_bytes_are_the_frame_exactly(self, tmp_path, torn):
        """Written in two parts, the file is byte for byte what the
        one-object frame was — and its first half where the 'ckpt.torn'
        drill fires (on the latest, hence on its link; the keep draws
        for itself)."""
        from fedtorch_tpu.robustness import host_chaos
        d = str(tmp_path)
        cfg, trainer, server, clients = make_experiment()
        server, clients, _ = trainer.run_round(server, clients)
        want = ckpt_mod._frame_payload(serialization.to_bytes(
            ckpt_mod._snapshot(server, clients, cfg)))
        assert want[:6] == b"FTCK1\x00" and len(want) > 46
        inj = host_chaos.HostFaultInjector(
            ("ckpt.torn",), rate=1.0, max_fires=1).install() \
            if torn else None
        try:
            save_checkpoint(d, server, clients, cfg, 0.5, is_best=True,
                            save_all=True)
        finally:
            if inj is not None:
                inj.uninstall()
        landed = want[:len(want) // 2] if torn else want
        assert _read(d, "checkpoint.ckpt") == landed
        assert _read(d, "model_best.ckpt") == landed
        assert _read(d, "checkpoint_r1.ckpt") == want
        if torn:    # the frame says so, and resume takes the keep
            assert ckpt_mod._unframe_payload(landed)[1] is not None
            s2, c2 = trainer.init_state(jax.random.key(0))
            with pytest.warns(RuntimeWarning, match="per-round keep"):
                s3, _, _, resumed = maybe_resume(d, s2, c2, cfg, None)
            assert resumed and int(jax.device_get(s3.round)) == 1

    def test_checkpoint_written_by_the_parents_code_resumes(
            self, tmp_path):
        """The on-disk format did not move: a file made the old way
        (the frame as one object, one plain write) resumes."""
        d = str(tmp_path)
        cfg, trainer, server, clients = make_experiment()
        server, clients, _ = trainer.run_round(server, clients)
        with open(os.path.join(d, "checkpoint.ckpt"), "wb") as f:
            f.write(ckpt_mod._frame_payload(serialization.to_bytes(
                ckpt_mod._snapshot(server, clients, cfg))))
        with open(os.path.join(d, "checkpoint.json"), "w") as f:
            json.dump(ckpt_mod._meta_for(cfg, 1, 0.25), f, default=str)
        s2, c2 = trainer.init_state(jax.random.key(0))
        s3, c3, best, resumed = maybe_resume(d, s2, c2, cfg, None)
        assert resumed and best == 0.25
        assert int(jax.device_get(s3.round)) == 1
        for got, want in zip(jax.tree.leaves(s3.params),
                             jax.tree.leaves(server.params)):
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(want))

    def test_every_payload_inode_is_fsynced_once_before_its_rename(
            self, tmp_path, monkeypatch):
        d = str(tmp_path)
        cfg, trainer, server, clients = make_experiment()
        server, clients, _ = trainer.run_round(server, clients)
        log = []
        real_fsync, real_replace = os.fsync, os.replace

        def fsync(fd):
            log.append(("fsync", os.fstat(fd).st_ino))
            return real_fsync(fd)

        def replace(src, dst, **kw):
            log.append(("publish", os.stat(src).st_ino,
                        os.path.basename(dst)))
            return real_replace(src, dst, **kw)

        monkeypatch.setattr(os, "fsync", fsync)
        monkeypatch.setattr(os, "replace", replace)
        save_checkpoint(d, server, clients, cfg, 0.5, is_best=True,
                        save_all=True)
        published = [e for e in log if e[0] == "publish"]
        assert [e[2] for e in published] == [
            "checkpoint.ckpt", "checkpoint.json", "model_best.ckpt",
            "model_best.json", "checkpoint_r1.ckpt"]
        for i, e in enumerate(log):
            if e[0] == "publish":   # durable before it gets its name
                assert ("fsync", e[1]) in log[:i], e
        payload_inodes = {e[1] for e in published
                          if e[2].endswith(".ckpt")}
        # three names, two inodes: the latest (and its link), the keep
        assert len(payload_inodes) == 2
        for ino in payload_inodes:
            assert log.count(("fsync", ino)) == 1
        assert sum(e[0] == "fsync" for e in log) == 4   # + two metas

    def test_save_all_keeps_are_files_of_their_own(self, tmp_path):
        """``maybe_resume`` falls back to the keeps when the latest
        frame is torn: they share no inode with it."""
        d = str(tmp_path)
        cfg, trainer, server, clients = make_experiment()
        server, clients, _ = trainer.run_round(server, clients)
        save_checkpoint(d, server, clients, cfg, 0.5, is_best=True,
                        save_all=True)
        latest, best, keep = (os.path.join(d, n) for n in (
            "checkpoint.ckpt", "model_best.ckpt", "checkpoint_r1.ckpt"))
        assert os.path.samefile(latest, best)
        assert not os.path.samefile(latest, keep)
        assert os.stat(keep).st_nlink == 1
        assert _read(d, "checkpoint_r1.ckpt") == _read(d,
                                                       "checkpoint.ckpt")

    def test_async_writer_links_too(self, tmp_path):
        from fedtorch_tpu.utils.checkpoint import AsyncCheckpointer
        d = str(tmp_path)
        cfg, trainer, server, clients = make_experiment()
        server, clients, _ = trainer.run_round(server, clients)
        saver = AsyncCheckpointer()
        try:
            saver.save(d, server, clients, cfg, 0.5, is_best=True)
            saver.wait()
        finally:
            saver.close()
        assert saver.stats()["ckpt_writes"] == 1.0
        assert os.path.samefile(os.path.join(d, "checkpoint.ckpt"),
                                os.path.join(d, "model_best.ckpt"))


# -- the payload as pieces that borrow the snapshot --------------------------
def _round_state():
    """The snapshot a save serializes: ServerState + ClientState of the
    small model after one round, numpy leaves that own their data."""
    cfg, trainer, server, clients = make_experiment()
    server, clients, _ = trainer.run_round(server, clients)
    return ckpt_mod._snapshot(server, clients, cfg)


def _client_minor(x):
    """``x`` as a TPU's ``device_get`` hands the per-client state over:
    the same values, the client axis minor-most in memory."""
    if x.ndim < 2:
        return x
    return np.moveaxis(np.ascontiguousarray(np.moveaxis(x, 0, -1)), -1, 0)


def _round_state_as_the_chip_lays_it_out():
    state = _round_state()
    return {"server": state["server"],
            "clients": jax.tree.map(_client_minor, state["clients"])}


def _device_state():
    """The same tree before the snapshot: jax arrays, which flax
    converts (and so does the walk)."""
    cfg, trainer, server, clients = make_experiment()
    return {"server": ckpt_mod._unkey(server), "clients": clients}


def _mixed_leaves():
    """Every kind of leaf the walk has to tell apart, sizes on both
    sides of msgpack's 8/16/32-bit headers and on its fixext lengths."""
    import ml_dtypes
    big = np.random.default_rng(0).standard_normal(
        (70, 40, 8)).astype(np.float32)
    tree = {
        "float32": big, "bfloat16": big[:3].astype(ml_dtypes.bfloat16),
        "int32": np.arange(7, dtype=np.int32),
        "key_data": np.asarray(jax.random.key_data(jax.random.key(3))),
        "zero_d": np.array(3, np.int32),
        "zero_size": np.zeros((0, 4), np.float32),
        "non_contiguous": big[:, ::2, :],
        "fortran": np.asfortranarray(big[0]),
        "numpy_scalar": np.float32(2.5),
        "int": 7, "float": 0.5, "str": "abc", "none": None, "bool": True,
        "complex": 1 + 2j,
        "nested": {"tuple": (np.ones(3), [np.zeros(2, np.int8)]),
                   "empty": {}},
        "bin16": np.ones(300, np.uint8), "bin32": np.ones(70000, np.uint8),
    }
    for n in range(1, 24):      # ext lengths 1..: the fixext widths
        tree[f"int8_{n}"] = np.zeros(n, np.int8)
        tree[f"col_{n}"] = np.zeros((n, 1, 1), np.uint16)
    return tree


class TestPayloadIsNeverBuilt:
    @pytest.mark.parametrize("make,chunk", [
        (_round_state, None),
        (_round_state_as_the_chip_lays_it_out, None),
        (_device_state, None),
        (_mixed_leaves, None), (_mixed_leaves, 1000)],
        ids=["round_state", "round_state_client_minor", "device_state",
             "mixed_leaves", "mixed_leaves_chunked"])
    def test_pieces_join_to_flax_to_bytes(self, monkeypatch, make, chunk):
        if chunk is not None:   # leaves over it flax splits into chunks
            monkeypatch.setattr(serialization, "MAX_CHUNK_SIZE", chunk)
        tree = make()
        want = serialization.to_bytes(tree)
        pieces, counts = ckpt_mod._payload_pieces(tree)
        assert b"".join(pieces) == want
        views = [p for p in pieces if isinstance(p, memoryview)]
        assert counts["borrowed_bytes"] + counts["relaid_bytes"] \
            == sum(len(v) for v in views)
        assert counts.pop("relaid_s") >= 0.0    # seconds, not bytes
        assert sum(counts.values()) <= len(want)
        assert ckpt_mod._frame_header(pieces) \
            == ckpt_mod._frame_payload(want)[:ckpt_mod._CKPT_HEADER]
        if chunk is not None:
            assert all(len(v) <= chunk for v in views)
            assert counts["copied_bytes"] > 70 * 40 * 8 * 4

    def test_borrowed_pieces_are_views_of_the_snapshot(self):
        state = _round_state()
        leaves = jax.tree.leaves(state)
        assert all(isinstance(x, np.ndarray) and x.size for x in leaves)
        pieces, counts = ckpt_mod._payload_pieces(state)
        views = [np.frombuffer(p, np.uint8) for p in pieces
                 if isinstance(p, memoryview)]
        # every leaf of the round state is borrowed, in flax's order,
        # none copied; the rest of the payload is msgpack text
        assert len(views) == len(leaves)
        assert counts == {
            "borrowed_bytes": sum(x.nbytes for x in leaves),
            "relaid_bytes": 0, "copied_bytes": 0, "relaid_s": 0.0}
        by_address = {x.__array_interface__["data"][0]: x
                      for x in leaves}
        for v in views:
            leaf = by_address[v.__array_interface__["data"][0]]
            assert np.shares_memory(v, leaf) and v.nbytes == leaf.nbytes
        text = sum(len(p) for p in pieces
                   if not isinstance(p, memoryview))
        assert text < 64 * len(leaves) + 256

    def test_strided_leaves_are_laid_out_once_not_packed_by_flax(self):
        """The chip's snapshot: the client state's memory is client-
        minor, so its C-order bytes are made by ONE copy each (counted
        as relaid, views of the copies) and flax's packer packs none."""
        state = _round_state_as_the_chip_lays_it_out()
        strided = [x for x in jax.tree.leaves(state["clients"])
                   if not x.flags.c_contiguous]
        assert strided
        pieces, counts = ckpt_mod._payload_pieces(state)
        assert counts["copied_bytes"] == 0
        assert counts["relaid_bytes"] == sum(x.nbytes for x in strided)
        assert counts["borrowed_bytes"] == sum(
            x.nbytes for x in jax.tree.leaves(state)
            if x.flags.c_contiguous)
        views = [np.frombuffer(p, np.uint8) for p in pieces
                 if isinstance(p, memoryview)]
        assert len(views) == len(jax.tree.leaves(state))
        for x in strided:
            assert not any(np.shares_memory(v, x) for v in views)

    def test_save_then_resume_is_bitwise(self, tmp_path, recorder):
        d = str(tmp_path / "ck")
        cfg, trainer, server, clients = make_experiment()
        server, clients, _ = trainer.run_round(server, clients)
        want = jax.device_get((ckpt_mod._unkey(server), clients))
        save_checkpoint(d, server, clients, cfg, 0.75, is_best=False)
        s2, c2 = trainer.init_state(jax.random.key(1))
        s3, c3, best, resumed = maybe_resume(d, s2, c2, cfg, None)
        assert resumed and best == 0.75
        got = jax.device_get((ckpt_mod._unkey(s3), c3))
        assert jax.tree.structure(got) == jax.tree.structure(want)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(a, b)
        # the span says the mechanism engaged, and on what
        (_, _, _, args), = [s for s in _spans(recorder)
                            if s[0] == "checkpoint.serialize"]
        assert args["copied_bytes"] == 0 and args["pieces"] > 2
        assert args["relaid_bytes"] == 0
        assert 0 < args["borrowed_bytes"] < os.path.getsize(
            os.path.join(d, "checkpoint.ckpt"))

    @pytest.mark.parametrize("where", ["inside_the_header",
                                       "at_a_piece_boundary",
                                       "inside_a_borrowed_piece"])
    def test_torn_cut_anywhere_in_the_list_is_refused(
            self, tmp_path, monkeypatch, where):
        """The drill cuts a list of pieces: the file is the frame's
        first ``lands`` bytes wherever the cut falls, resume refuses
        it and the previous keep restores."""
        from fedtorch_tpu.robustness import host_chaos
        d = str(tmp_path)
        cfg, trainer, server, clients = make_experiment()
        server, clients, _ = trainer.run_round(server, clients)
        save_checkpoint(d, server, clients, cfg, 0.5, is_best=False,
                        save_all=True)
        server, clients, _ = trainer.run_round(server, clients)
        snapshot = ckpt_mod._snapshot(server, clients, cfg)
        pieces, _ = ckpt_mod._payload_pieces(snapshot)
        assert isinstance(pieces[1], memoryview) and len(pieces[1]) > 1
        boundary = ckpt_mod._CKPT_HEADER + len(pieces[0])
        lands = {"inside_the_header": 20,
                 "at_a_piece_boundary": boundary,
                 "inside_a_borrowed_piece":
                     boundary + len(pieces[1]) // 2}[where]
        want = ckpt_mod._frame_payload(serialization.to_bytes(snapshot))
        assert lands < len(want)
        monkeypatch.setattr(host_chaos, "torn_length",
                            lambda seam, size: lands)
        save_checkpoint(d, server, clients, cfg, 0.5, is_best=False)
        monkeypatch.undo()
        landed = _read(d, "checkpoint.ckpt")
        assert landed == want[:lands]
        assert ckpt_mod._unframe_payload(landed)[1] is not None
        assert not ckpt_mod.frame_quick_ok(
            os.path.join(d, "checkpoint.ckpt"))
        s2, c2 = trainer.init_state(jax.random.key(0))
        with pytest.warns(RuntimeWarning, match="per-round keep"):
            s3, _, _, resumed = maybe_resume(d, s2, c2, cfg, None)
        assert resumed and int(jax.device_get(s3.round)) == 1

    def test_retried_write_attempt_writes_the_full_file(
            self, tmp_path, monkeypatch):
        """An attempt that failed AFTER it had walked the pieces (its
        fsync) is retried under 'ckpt.write', and the retry walks the
        same list again: the whole frame lands, not an exhausted
        iterator's nothing."""
        from fedtorch_tpu.robustness import host_recovery
        d = str(tmp_path)
        cfg, trainer, server, clients = make_experiment()
        server, clients, _ = trainer.run_round(server, clients)
        want = ckpt_mod._frame_payload(serialization.to_bytes(
            ckpt_mod._snapshot(server, clients, cfg)))
        real_fsync, failed = os.fsync, []

        def fsync(fd):
            if not failed:
                failed.append(os.fstat(fd).st_size)
                raise OSError(errno.EIO, "injected: fsync failed")
            return real_fsync(fd)

        monkeypatch.setattr(os, "fsync", fsync)
        ledger = host_recovery.HostRecovery(
            sleep_fn=lambda s: None).install()
        try:
            save_checkpoint(d, server, clients, cfg, 0.5, is_best=True,
                            save_all=True)
        finally:
            ledger.uninstall()
        assert failed == [len(want)]    # the first attempt wrote it all
        assert ledger.stats()["host_retries"] == 1
        for name in ("checkpoint.ckpt", "model_best.ckpt",
                     "checkpoint_r1.ckpt"):
            assert _read(d, name) == want


# -- the save's span tree (ISSUE 37) ------------------------------------------
# the spans under which a save does its work; the rest only hold them
UNSPANNED_GAP_US = 20_000
LEAF_SPANS = ("checkpoint.snapshot", "checkpoint.layout",
              "checkpoint.digest", "checkpoint.file_write.data",
              "checkpoint.file_write.fsync", "checkpoint.file_write.rename",
              "checkpoint.link", "checkpoint.gc")


def _inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def _check_save_tree(spans, d, files):
    """One best save with a keep: ``files`` in the order they are
    written; nesting and args of every span below ``checkpoint.write``
    and of the snapshot."""
    (snap,), (write,), (ser,), (layout,), (digest,), (link,), (gc,) = (
        _named(spans, n) for n in (
            "checkpoint.snapshot", "checkpoint.write",
            "checkpoint.serialize", "checkpoint.layout",
            "checkpoint.digest", "checkpoint.link", "checkpoint.gc"))
    payload = os.path.getsize(os.path.join(d, "checkpoint.ckpt")) \
        - ckpt_mod._CKPT_HEADER
    # the snapshot: before the write, with what it holds
    assert snap[2] <= write[1]
    assert set(snap[3]) == {"bytes", "leaves", "owned_copy_bytes"}
    assert 0 < snap[3]["bytes"] < payload and snap[3]["leaves"] > 2
    # on the CPU device_get hands back views: every byte is copied
    assert snap[3]["owned_copy_bytes"] == snap[3]["bytes"]
    # serialize = layout then digest, its own args as they were
    assert _inside(ser, write)
    assert _inside(layout, ser) and _inside(digest, ser)
    assert layout[2] <= digest[1]
    assert set(ser[3]) == {"pieces", "borrowed_bytes", "relaid_bytes",
                           "copied_bytes"}
    assert set(layout[3]) == (set(ser[3]) - {"pieces"}) | {"relaid_s"}
    assert layout[3]["borrowed_bytes"] == snap[3]["bytes"]
    assert layout[3]["relaid_s"] == 0.0     # nothing strided on the CPU
    assert digest[3] == {"bytes": payload}
    # one file_write a file, each one attempt of data, fsync, rename
    writes = _named(spans, "checkpoint.file_write")
    assert [w[3]["name"] for w in writes] == files
    for w in writes:
        assert _inside(w, write) and w[3]["attempts"] == 1
        assert set(w[3]) == {"name", "bytes", "attempts"}
        parts = [s for s in spans if s[0].startswith(
            "checkpoint.file_write.") and _inside(s, w)]
        assert [p[0] for p in parts] == [
            "checkpoint.file_write.data", "checkpoint.file_write.fsync",
            "checkpoint.file_write.rename"]
        assert all(p[3] == {} for p in parts)
        assert parts[0][2] <= parts[1][1] and parts[1][2] <= parts[2][1]
    assert len(_named(spans, "checkpoint.file_write.data")) == len(files)
    assert _inside(link, write) and link[3] == {
        "name": "model_best.ckpt", "fallback": False}
    assert _inside(gc, write) and gc[3] == {}
    assert writes[-1][2] <= gc[1]


SAVE_FILES = ["checkpoint.ckpt", "checkpoint.json", "model_best.json",
              "checkpoint_r1.ckpt"]


class TestSaveSpanTree:
    def test_synchronous_save(self, tmp_path, recorder):
        d = str(tmp_path / "ck")
        cfg, trainer, server, clients = make_experiment(
            ckpt_kw={"keep_last_n": 2})
        server, clients, _ = trainer.run_round(server, clients)
        save_checkpoint(d, server, clients, cfg, 0.5, is_best=True,
                        save_all=True)
        spans = _spans(recorder)
        _check_save_tree(spans, d, SAVE_FILES)
        assert len({e["tid"] for e in recorder.spans.to_trace_events()
                    if e["name"].startswith("checkpoint")}) == 1

    def test_async_save_gets_the_same_tree_on_its_lane(
            self, tmp_path, recorder):
        from fedtorch_tpu.utils.checkpoint import AsyncCheckpointer
        d = str(tmp_path / "ck")
        cfg, trainer, server, clients = make_experiment(
            ckpt_kw={"keep_last_n": 2})
        server, clients, _ = trainer.run_round(server, clients)
        saver = AsyncCheckpointer()
        try:
            saver.save(d, server, clients, cfg, 0.5, is_best=True,
                       save_all=True)
            saver.wait()
        finally:
            saver.close()
        _check_save_tree(_spans(recorder), d, SAVE_FILES)
        lanes = {}
        for e in recorder.spans.to_trace_events():
            if e.get("ph") == "X" and e["name"].startswith("checkpoint"):
                lanes.setdefault(e["tid"], set()).add(e["name"])
        # the snapshot on the caller's thread, the rest on the writer's
        assert sorted(lanes.values(), key=len)[0] == {"checkpoint.snapshot"}
        assert len(lanes) == 2

    def test_retried_write_shows_as_two_attempts(self, tmp_path, recorder):
        """The ``ckpt.write`` drill fails the payload file's first
        attempt: two ``checkpoint.file_write.data`` under ONE
        ``checkpoint.file_write`` whose ``attempts`` is 2, and one
        fsync and rename, the retry's."""
        from fedtorch_tpu.robustness import host_chaos, host_recovery
        d = str(tmp_path / "ck")
        cfg, trainer, server, clients = make_experiment()
        server, clients, _ = trainer.run_round(server, clients)
        ledger = host_recovery.HostRecovery(
            sleep_fn=lambda s: None).install()
        inj = host_chaos.HostFaultInjector(
            ("ckpt.write",), rate=1.0, max_fires=1).install()
        try:
            save_checkpoint(d, server, clients, cfg, 0.5, is_best=False)
        finally:
            inj.uninstall()
            ledger.uninstall()
        assert ledger.stats()["host_retries"] == 1
        spans = _spans(recorder)
        first, meta = _named(spans, "checkpoint.file_write")
        assert first[3]["name"] == "checkpoint.ckpt"
        assert first[3]["attempts"] == 2 and meta[3]["attempts"] == 1
        assert [s[0] for s in spans if s[0].startswith(
            "checkpoint.file_write.") and _inside(s, first)] == [
                "checkpoint.file_write.data", "checkpoint.file_write.data",
                "checkpoint.file_write.fsync",
                "checkpoint.file_write.rename"]
        assert ckpt_mod.frame_quick_ok(os.path.join(d, "checkpoint.ckpt"))

    def test_leaf_spans_tile_the_loops_checkpoint_span(
            self, tmp_path, recorder):
        """What the save does outside its leaf spans (the meta's JSON,
        directory and drill bookkeeping, the reads of the resident
        set) is a few milliseconds whatever the state's size: every
        leaf lies inside the loop's ``checkpoint`` span, none overlaps
        the next, and the time between them stays under a fixed cap, so
        work moved out from under its span shows."""
        d = str(tmp_path / "ck")
        cfg, trainer, server, clients = make_experiment(
            ckpt_kw={"keep_last_n": 2})
        server, clients, _ = trainer.run_round(server, clients)
        # as the launcher's loop opens it
        with recorder.span("checkpoint", round=0).rss():
            save_checkpoint(d, server, clients, cfg, 0.5, is_best=True,
                            save_all=True)
        spans = _spans(recorder)
        (whole,) = _named(spans, "checkpoint")
        leaves = sorted((s for s in spans if s[0] in LEAF_SPANS),
                        key=lambda s: s[1])
        assert {s[0] for s in leaves} == set(LEAF_SPANS)
        edges = [whole[1]] + [t for s in leaves for t in s[1:3]] \
            + [whole[2]]
        assert edges == sorted(edges)
        gaps = [b - a for a, b in zip(edges[::2], edges[1::2])]
        # microseconds: the widest gap (the meta's JSON), and all of them
        assert max(gaps) < UNSPANNED_GAP_US
        assert sum(gaps) < 3 * UNSPANNED_GAP_US
        assert set(whole[3]) == {"round"} | (
            {"vm_rss_enter", "vm_rss_exit"}
            if os.path.exists("/proc/self/status") else set())
