"""Data layer: datasets, partitioners, device-side batching.

``build_federated_data`` is the one-call equivalent of the reference's
``define_dataset`` + ``FederatedPartitioner`` pipeline
(components/dataset.py:39-231): load -> partition (scheme chosen exactly
as partition.py:106-220 does) -> optional per-client train/val split for
personalization -> stack into padded ``[clients, N, ...]`` device arrays.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import numpy as np

from fedtorch_tpu import telemetry
from fedtorch_tpu.config import ExperimentConfig
from fedtorch_tpu.data.batching import (  # noqa: F401
    ClientData, epoch_permutation, growing_batch_schedule, sample_batch,
    stack_partitions, take_batch, train_val_split,
)
from fedtorch_tpu.data.datasets import DatasetSplits, get_dataset  # noqa: F401
from fedtorch_tpu.data.streaming import (  # noqa: F401
    HostClientStore, RoundFeed, StreamFeedProducer, feed_nbytes,
)
from fedtorch_tpu.data.partition import (  # noqa: F401
    dirichlet_partition, growing_batch_partition, iid_partition,
    label_sorted_partition, partition_sizes, sensitive_group_partition,
)
from fedtorch_tpu.data.synthetic import generate_synthetic  # noqa: F401


class FederatedData(NamedTuple):
    train: ClientData
    val: Optional[ClientData]      # per-client validation (fed_personal)
    test_x: np.ndarray             # server-side test set
    test_y: np.ndarray
    num_clients: int


def choose_partitions(splits: DatasetSplits, cfg: ExperimentConfig,
                      num_clients: int):
    """Partition-scheme dispatch (partition.py:106-220)."""
    d = cfg.data
    if splits.client_partitions is not None:
        # naturally-federated (emnist/shakespeare/synthetic): client i's
        # file is its partition; when there are more natural clients than
        # requested, take the first num_clients (the reference maps one
        # rank per client file).
        parts = splits.client_partitions
        if len(parts) < num_clients:
            raise ValueError(
                f"dataset provides {len(parts)} natural clients < "
                f"requested {num_clients}")
        return parts[:num_clients]
    if d.dataset == "adult" and splits.sensitive_values is not None \
            and not d.iid:
        return sensitive_group_partition(splits.sensitive_values,
                                         num_clients)
    if d.iid:
        return iid_partition(len(splits.train_y), num_clients,
                             seed=cfg.train.manual_seed)
    if d.dirichlet:
        return dirichlet_partition(splits.train_y, num_clients,
                                   concentration=d.dirichlet_alpha,
                                   seed=cfg.train.manual_seed)
    return label_sorted_partition(splits.train_y, num_clients,
                                  num_class_per_client=d.num_class_per_client,
                                  unbalanced=d.unbalanced)


def build_federated_data(cfg: ExperimentConfig,
                         download: bool = False) -> FederatedData:
    num_clients = cfg.federated.num_clients
    with telemetry.span("data.load"):
        splits = get_dataset(cfg.data, num_clients, download=download,
                             seq_len=cfg.model.rnn_seq_len)
    with telemetry.span("data.partition"):
        parts = choose_partitions(splits, cfg, num_clients)
        if cfg.federated.personal:
            parts, val_parts = train_val_split(
                parts, cfg.data.val_fraction, seed=cfg.train.manual_seed)

    with telemetry.span("data.layout"):
        val = stack_partitions(splits.train_x, splits.train_y, val_parts) \
            if cfg.federated.personal else None
        train = stack_partitions(splits.train_x, splits.train_y, parts)
    return FederatedData(train=train, val=val, test_x=splits.test_x,
                         test_y=splits.test_y, num_clients=num_clients)
