"""The seam between the engine and a model's gauges (PR 44;
``models/common.py``: ``is_token_model``): a token model names its own
row keys (``gauge_names``), makes their values of its loss's parts
inside the round (``round_gauges``) and knows what the traced step
takes (``trace_gauges``); ``core/state.py``, ``parallel/federated.py``
and ``cli.py`` name none of them. Over the four language configurations
of ``benchmark/configs/`` at their tests' widths and one conv model."""
import json
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_kanana_lm
import test_keye_lm
import test_looped_lm
import test_sequential_round
from benchmark.datagen import tokens as token_files
from fedtorch_tpu.algorithms import make_algorithm
from fedtorch_tpu.cli import build_parser, run_experiment
from fedtorch_tpu.config import (
    DataConfig, ExperimentConfig, FederatedConfig, MeshConfig, ModelConfig,
    OptimConfig, TrainConfig,
)
from fedtorch_tpu.core.state import RoundMetrics
from fedtorch_tpu.data import build_federated_data
from fedtorch_tpu.models import define_model
from fedtorch_tpu.models.hybrid_lm import HybridLM, load_spec
from fedtorch_tpu.parallel import FederatedTrainer
from fedtorch_tpu.telemetry import schema
from test_sequential_round import gauges_of, lm_cfg, round_rows

# configuration -> (its small specification, the keys its rounds' rows
# carried for it at the parent of PR 44: what ``gauge_names`` must give)
LANGUAGE = {
    "olmo_hybrid_7b_l4": (test_sequential_round.SPEC, ()),
    "ouro_2_6b_l8": (test_looped_lm.SMALL,
                     ("lm_exit_mass_last", "lm_exit_entropy")),
    "keye_vl2_30b_a3b_l4": (test_keye_lm.SMALL, (
        "lm_moe_pairs_local", "lm_moe_load_max_over_mean",
        "lm_moe_rows_visited", "lm_index_loss")),
    "kanana2_30b_a3b_l5": (test_kanana_lm.SMALL, (
        "lm_moe_pairs_local", "lm_moe_load_max_over_mean",
        "lm_moe_rows_visited", "lm_router_load_max_over_mean",
        "lm_router_bias_abs_max", "lm_balance_loss")),
}
# the leaves of a round's metrics before any model's gauges: three
# per-client vectors and twelve scalars
BASE_LEAVES = 15
CONV = "conv"
CLIENTS = 6


@pytest.fixture(scope="module")
def files(tmp_path_factory):
    """configuration -> {"spec", "data"} at the tests' widths."""
    out = {}
    for name, (small, _) in LANGUAGE.items():
        root = tmp_path_factory.mktemp(name)
        spec = root / "spec.json"
        spec.write_text(json.dumps(
            {k: v for k, v in small.items() if v is not ...}))
        out[name] = {"spec": str(spec), "data": token_files.write(
            str(root / "data"), 7, clients=CLIENTS, rows_per_client=5,
            seq_len=24, vocab_size=small["vocab_size"], test_rows=3)}
    return out


def conv_cfg(run_dir=None):
    from fedtorch_tpu.config import CheckpointConfig
    return ExperimentConfig(
        data=DataConfig(dataset="synthetic", synthetic_dim=12,
                        batch_size=8),
        federated=FederatedConfig(
            federated=True, num_clients=CLIENTS, online_client_rate=0.5,
            algorithm="fedavg", sync_type="local_step", num_comms=1),
        model=ModelConfig(arch="logistic_regression"),
        optim=OptimConfig(lr=0.1, weight_decay=0.0),
        train=TrainConfig(local_step=2, eval_freq=1, manual_seed=11),
        checkpoint=CheckpointConfig(run_dir=run_dir),
        mesh=MeshConfig(num_devices=1),
    ).finalize()


def cfg_of(name, files, **kw):
    return conv_cfg(**kw) if name == CONV \
        else lm_cfg(files[name], "sequential", num_comms=1, **kw)


def model_keys(row) -> set:
    """A row's keys that are a model's own (``lm_*``, ``ut_steps``)."""
    return {k for k in row if k.startswith("lm_") or k == "ut_steps"}


MODELS = list(LANGUAGE) + [CONV]


# -- (a) the names are the row's keys ---------------------------------------------

@pytest.mark.parametrize("name", MODELS)
def test_gauge_names_are_the_rows_model_keys(name, files, tmp_path):
    """A launcher round's row carries, of a model's own keys, exactly
    ``gauge_names`` and ``trace_gauges``' keys, every one cataloged;
    the names are those the engine's tables held before PR 44."""
    run_dir = str(tmp_path / "run")
    cfg = cfg_of(name, files, run_dir=run_dir)
    run_experiment(cfg)
    (row,) = round_rows(run_dir)
    model = define_model(cfg, batch_size=cfg.data.batch_size)
    names = tuple(getattr(model, "gauge_names", ()))
    traced = model.trace_gauges(cfg.data.batch_size, 24) \
        if name != CONV else {}
    assert names == LANGUAGE.get(name, (None, ()))[1]
    assert model_keys(row) - set(traced) == set(names)
    assert set(traced) <= set(row)
    assert all(row[k] == v for k, v in traced.items())
    assert set(names) | set(traced) <= set(schema.METRICS_OPTIONAL)
    schema.validate_metrics_row(row)


# -- (b) no gauges, no leaves ------------------------------------------------------

@pytest.mark.parametrize("name", MODELS)
def test_metrics_flatten_to_the_leaves_they_had(name, files):
    """``RoundMetrics`` carries the model's gauges as ONE field: None
    (no leaf) for a model without any, so its round program's results
    are as they were; else one scalar a name, after every other leaf
    and in the names' order."""
    cfg = cfg_of(name, files)
    data = build_federated_data(cfg).train
    t = FederatedTrainer(cfg, define_model(
        cfg, batch_size=cfg.data.batch_size), make_algorithm(cfg), data)
    server, clients = t.init_state(jax.random.key(0))
    _, _, m = t.run_round(server, clients)
    leaves = jax.tree.leaves(m)
    assert len(leaves) == BASE_LEAVES + len(t.gauge_names)
    assert [f for f in RoundMetrics._fields if f.startswith("lm_")] == []
    if name == CONV:    # the option's default: 'auto' is the vmapped round
        assert cfg.mesh.client_fusion == "auto" and t.client_fusion == "vmap"
    if not t.gauge_names:
        assert m.model_gauges is None
        return
    assert RoundMetrics._fields[-1] == "model_gauges"
    assert all(a is b for a, b in zip(leaves[BASE_LEAVES:],
                                      m.model_gauges))
    assert all(g.shape == () and g.dtype == jnp.float32
               for g in m.model_gauges)


@pytest.mark.parametrize("name", MODELS)
def test_gauges_ride_the_rounds_one_scalar_fetch(name, files):
    """The model's names follow the engine's own keys in the round's
    ONE scalar program (traced once) and its ONE array: the values the
    round's metrics hold, under the model's names, bit for bit."""
    from fedtorch_tpu.parallel.federated import (
        _COMPUTED_SCALARS, _SCALAR_FIELDS,
    )
    from fedtorch_tpu.utils.tracing import RecompilationSentinel
    cfg = cfg_of(name, files)
    t = FederatedTrainer(cfg, define_model(
        cfg, batch_size=cfg.data.batch_size), make_algorithm(cfg),
        build_federated_data(cfg).train)
    server, clients = t.init_state(jax.random.key(1))
    with RecompilationSentinel() as sentinel:
        for _ in range(2):
            server, clients, m = t.run_round(server, clients)
            scalars = t.round_host_scalars(clients, m)
    assert sentinel.counts[t.scalars_trace_name] == 1
    engine = [key for key, field in _SCALAR_FIELDS
              if getattr(m, field) is not None]
    assert list(scalars) == list(_COMPUTED_SCALARS) + engine \
        + list(t.gauge_names)
    for key, leaf in gauges_of(t, m).items():
        assert scalars[key] == float(leaf)


# -- (c) the values are the parts' means -------------------------------------------

def hand_parts(k=2, K=3, R=3):
    """Parts as the sequential round stacks them, [k, K, ...]."""
    rng = np.random.RandomState(5)
    keys = {"exit_mass": (k, K, R), "exit_ce": (k, K, R)}
    names = ("exit_entropy", "ce", "index_loss", "moe_pairs",
             "moe_rows_visited", "moe_load_max_over_mean", "balance_loss",
             "router_load_max_over_mean", "router_bias_abs_max")
    return {key: jnp.asarray(rng.rand(*keys.get(key, (k, K))), jnp.float32)
            for key in tuple(keys) + names}


PART_OF = {
    "lm_exit_entropy": "exit_entropy", "lm_moe_pairs_local": "moe_pairs",
    "lm_moe_load_max_over_mean": "moe_load_max_over_mean",
    "lm_moe_rows_visited": "moe_rows_visited",
    "lm_index_loss": "index_loss",
    "lm_router_load_max_over_mean": "router_load_max_over_mean",
    "lm_router_bias_abs_max": "router_bias_abs_max",
    "lm_balance_loss": "balance_loss",
}


@pytest.mark.parametrize("name", list(LANGUAGE))
def test_round_gauges_are_the_parts_means(name, files):
    """Each gauge is the mean of its part over the round's clients and
    steps; the looped model's exit mass is its last pass's."""
    model = HybridLM("hybrid_lm", load_spec(files[name]["spec"]),
                     dtype="float32", attention="auto", remat=True)
    parts = hand_parts()
    got = dict(zip(model.gauge_names, model.round_gauges(parts)))
    assert tuple(got) == LANGUAGE[name][1]
    for key, value in got.items():
        want = np.mean(np.asarray(parts["exit_mass"])[..., -1]) \
            if key == "lm_exit_mass_last" \
            else np.mean(np.asarray(parts[PART_OF[key]]))
        assert value.dtype == jnp.float32 and value.shape == ()
        np.testing.assert_allclose(float(value), want, rtol=1e-6)


# -- (d) a model of the test's own ---------------------------------------------------

class Bigram(NamedTuple):
    """A token model the engine has never heard of: next-token logits
    from one table, one gauge of its loss and one of its trace."""
    name: str = "bigram"
    module: tuple = ("bigram", 64)      # the evaluation cache's key
    eval_batch: int = 1
    is_recurrent: bool = False
    is_regression: bool = False
    has_noise_param: bool = False
    has_aux_loss: bool = False
    gauge_names: tuple = ("toy_mean_token",)

    def init(self, rng):
        return {"table": 0.02 * jax.random.normal(rng, (64, 64))}

    def apply(self, params, x, train=False, rng=None, carry=None):
        return params["table"][x]

    def token_loss_parts(self, params, x, train=False, rng=None):
        logp = jax.nn.log_softmax(self.apply(params, x)[:, :-1])
        nll = -jnp.take_along_axis(logp, x[:, 1:, None], axis=-1)[..., 0]
        hit = jnp.argmax(logp, axis=-1) == x[:, 1:]
        return jnp.mean(nll), jnp.mean(hit.astype(jnp.float32)), {
            "mean_token": jnp.mean(x.astype(jnp.float32))}

    def token_loss(self, params, x, train=False, rng=None):
        return self.token_loss_parts(params, x, train, rng)[:2]

    def init_carry(self, batch_size):
        return None

    def round_gauges(self, parts):
        return (jnp.mean(parts["mean_token"]),)

    def trace_gauges(self, rows, tokens):
        return {"toy_row_tokens": float(rows * tokens)}


def test_a_models_own_gauge_reaches_the_row(files, tmp_path, monkeypatch):
    """The seam's point: a token model defined HERE puts a key from its
    loss and a key from its trace on the launcher's row, and its value
    through the round's one scalar fetch, with no edit of the engine."""
    import fedtorch_tpu.models as models
    monkeypatch.setattr(models, "define_model",
                        lambda cfg, batch_size=2: Bigram())
    run_dir = str(tmp_path / "run")
    run_experiment(lm_cfg(files["olmo_hybrid_7b_l4"], "sequential",
                          run_dir=run_dir, num_comms=2))
    rows = round_rows(run_dir)
    assert len(rows) == 2
    assert all(0.0 <= r["toy_mean_token"] < 64.0
               and r["toy_row_tokens"] == 24.0
               and r["tokens_trained"] == 3 * 2 * 24 for r in rows)
    assert not any(k.startswith("lm_") for r in rows for k in r)
    # the engine's own view of the same round
    cfg = lm_cfg(files["olmo_hybrid_7b_l4"], "sequential")
    t = FederatedTrainer(cfg, Bigram(), make_algorithm(cfg),
                         build_federated_data(cfg).train)
    server, clients = t.init_state(jax.random.key(3))
    server, clients, m = t.run_round(server, clients)
    scalars = t.round_host_scalars(clients, m)
    assert scalars["toy_mean_token"] == float(
        gauges_of(t, m)["toy_mean_token"])
    # under vmap the parts are not asked for: no key, no leaf
    tv = FederatedTrainer(lm_cfg(files["olmo_hybrid_7b_l4"], "vmap"),
                          Bigram(), make_algorithm(cfg),
                          build_federated_data(cfg).train)
    assert tv.gauge_names == () and "toy_row_tokens" in tv.telemetry_gauges()


# -- (e) the option's retired value ----------------------------------------------------

@pytest.mark.parametrize("value,ok", [
    ("auto", True), ("vmap", True), ("sequential", True),
    ("fused", False)])
def test_client_fusion_values(value, ok, capsys):
    """``--client_fusion fused`` went with the execution it chose (PR
    44): the parser refuses it like any unknown value, and so does the
    configuration."""
    argv = ["--client_fusion", value]
    if ok:
        assert build_parser().parse_args(argv).client_fusion == value
        MeshConfig(client_fusion=value)
        return
    with pytest.raises(SystemExit):
        build_parser().parse_args(argv)
    assert "invalid choice: 'fused'" in capsys.readouterr().err
    with pytest.raises(ValueError, match="client_fusion must be"):
        ExperimentConfig(mesh=MeshConfig(client_fusion=value)).finalize()


def test_the_engine_names_no_model_gauge():
    """``core/state.py``, ``parallel/federated.py`` and ``cli.py`` hold
    no ``lm_*`` key outside comments and docstrings, and
    ``telemetry_gauges`` probes the model with no ``hasattr``."""
    import ast
    import inspect

    import fedtorch_tpu.cli
    import fedtorch_tpu.core.state
    import fedtorch_tpu.parallel.federated as federated
    for mod in (fedtorch_tpu.core.state, federated, fedtorch_tpu.cli):
        tree = ast.parse(inspect.getsource(mod))
        docs = {id(n.body[0].value) for n in ast.walk(tree)
                if isinstance(n, (ast.Module, ast.ClassDef,
                                  ast.FunctionDef))
                and n.body and isinstance(n.body[0], ast.Expr)}
        named = [n.value for n in ast.walk(tree)
                 if isinstance(n, ast.Constant) and isinstance(n.value, str)
                 and id(n) not in docs and "lm_" in n.value]
        named += [n.id for n in ast.walk(tree)
                  if isinstance(n, ast.Name) and n.id.startswith("lm_")]
        named += [n.attr for n in ast.walk(tree)
                  if isinstance(n, ast.Attribute)
                  and n.attr.startswith("lm_")]
        assert named == [], (mod.__name__, named)
    gauges = inspect.getsource(federated.FederatedTrainer.telemetry_gauges)
    assert "hasattr" not in gauges
