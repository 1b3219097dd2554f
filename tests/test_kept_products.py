"""What a rematerialized layer of ``models/hybrid_lm.py`` keeps: the
gradients are the same whatever set of matrix products is kept; the
backward pass runs no kept product again; and the chooser
(``kept_products`` within ``residual_budget``) as a pure function, at
small shapes and at the two benchmark cells' published widths."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_hybrid_lm
import test_looped_lm
from fedtorch_tpu.models import hybrid_lm
from fedtorch_tpu.models.hybrid_lm import (
    HybridLM, kept_counters, kept_products, layer_products, load_spec,
    residual_budget,
)

GIB = 1 << 30
# the small specifications of the two families; the looped one once
# more without rotary embedding (see the bit-equality test)
SPECS = {
    "hybrid": test_hybrid_lm.SMALL,
    "looped": test_looped_lm.SMALL,
    "looped_no_rotary": dict(test_looped_lm.SMALL, rope_theta=None),
}
# named products in one trace of the layers (a looped model's scan body
# is traced once): a delta-rule layer's 10 and a full-attention
# layer's 7; 2 x 7 and the head under the exits' own checkpoint
REMATTED_TODAY = {"hybrid": 17, "looped": 15}


def spec_of(tmp_path, name):
    path = tmp_path / f"{name}.json"
    path.write_text(json.dumps(SPECS[name]))
    return load_spec(str(path))


def sets_of(spec):
    """The empty, a partial and the full set."""
    full = kept_products(spec, 1, 128, None)
    return {"empty": (), "partial": full[:3], "full": full}


def loss_and_grads(monkeypatch, spec, kept, dtype, remat=True):
    """Loss and gradients of one 128-token row with ``kept`` forced."""
    monkeypatch.setattr(hybrid_lm, "_kept_for", lambda s, r, t: kept)
    model = HybridLM("hybrid_lm", spec, dtype=dtype, attention="auto",
                     remat=remat)
    params = model.init(jax.random.key(1))
    x = jnp.asarray(np.random.RandomState(0).randint(
        0, spec.vocab_size, (1, 128)), jnp.int32)
    f = jax.value_and_grad(lambda p: model.token_loss(p, x)[0])
    return jax.jit(f)(params), jax.make_jaxpr(f)(params)


def rematted_products(jaxpr):
    """``dot_general``s without batch dimensions (every named product
    is one; attention's and the delta rule's are batched) that the
    backward pass runs again: JAX puts ``rematted_computation`` on the
    name stack of what a checkpoint's transpose recomputes."""
    count = 0
    for eqn in jaxpr.eqns:
        for sub in jax.core.jaxprs_in_params(eqn.params):
            count += rematted_products(sub)
        if eqn.primitive.name == "dot_general" \
                and not eqn.params["dimension_numbers"][1][0] \
                and "rematted_computation" in str(
                    eqn.source_info.name_stack):
            count += 1
    return count


@pytest.mark.parametrize("name", ["hybrid", "looped_no_rotary"])
def test_gradients_are_the_same_bits_whatever_is_kept(
        tmp_path, monkeypatch, name):
    """bfloat16 operands: a product kept and a product run again are
    the same bits, so loss and every gradient leaf are too. (With
    rotary embedding in a looped model this backend's LLVM contracts
    the turn's multiply-add or not by what it is fused with, an ulp
    that bfloat16's rounding of the next operand then flips: that
    specification is held to float32's rounding in the next test.)"""
    spec = spec_of(tmp_path, name)
    got = {label: loss_and_grads(monkeypatch, spec, kept, "bfloat16")[0]
           for label, kept in sets_of(spec).items()}
    for label in ("partial", "full"):
        np.testing.assert_array_equal(got[label][0], got["empty"][0])
        for a, b in zip(jax.tree.leaves(got[label][1]),
                        jax.tree.leaves(got["empty"][1])):
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("name", ["hybrid", "looped"])
def test_gradients_equal_the_plain_layers_whatever_is_kept(
        tmp_path, monkeypatch, name):
    """float32: every set against ``remat=False`` within the tolerance
    the models' own tests hold ``remat`` to."""
    spec = spec_of(tmp_path, name)
    (want, want_grads), _ = loss_and_grads(monkeypatch, spec, (),
                                           "float32", remat=False)
    for kept in sets_of(spec).values():
        (loss, grads), _ = loss_and_grads(monkeypatch, spec, kept,
                                          "float32")
        np.testing.assert_allclose(loss, want, rtol=1e-6)
        worst, gaps = test_looped_lm.worst_gap(grads, want_grads)
        assert worst < 1e-5, gaps


@pytest.mark.parametrize("name", ["hybrid", "looped"])
def test_the_backward_pass_runs_no_kept_product_again(
        tmp_path, monkeypatch, name):
    spec = spec_of(tmp_path, name)
    sizes = [len(layer_products(spec, k)) for k in spec.layer_types]
    # what this PR leaves alone: the head under the exits' checkpoint
    rest = REMATTED_TODAY[name] - sum(sizes)
    for label, kept in sets_of(spec).items():
        _, jaxpr = loss_and_grads(monkeypatch, spec, kept, "bfloat16")
        again = sum(1 for k in spec.layer_types
                    for n in layer_products(spec, k) if n not in kept)
        assert rematted_products(jaxpr.jaxpr) == again + rest, label
        if label == "empty":
            assert again + rest == REMATTED_TODAY[name]
        if label == "full":
            assert again == 0


def test_without_remat_nothing_is_chosen_or_run_again(tmp_path,
                                                      monkeypatch):
    def refuse(*a):
        raise AssertionError("the chooser ran without remat")
    monkeypatch.setattr(hybrid_lm, "_kept_for", refuse)
    spec = spec_of(tmp_path, "hybrid")
    model = HybridLM("hybrid_lm", spec, dtype="bfloat16",
                     attention="auto", remat=False)
    x = jnp.zeros((1, 16), jnp.int32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: model.token_loss(p, x)[0]))(model.init(jax.random.key(0)))
    assert rematted_products(jaxpr.jaxpr) == 0
    assert model.trace_gauges(1, 16) == {}


# -- the chooser -------------------------------------------------------------

def test_order_is_by_inner_dimension_then_outputs_first(tmp_path):
    """FLOPs spared a byte kept are ``K / 2``: ``mlp.down`` (K 48)
    first; ``mixer.o`` is K 24 in the delta-rule layer and 32 in the
    full-attention one (28 a byte over both), so it comes last."""
    spec = spec_of(tmp_path, "hybrid")
    assert kept_products(spec, 1, 128, None) == (
        "mlp.down", "mixer.q", "mixer.k", "mixer.v", "mixer.b",
        "mixer.a", "mixer.g", "mlp.gate", "mlp.up", "mixer.o")
    # equal inner dimensions (32): the sublayer's output, whose result
    # the forward pass writes anyway, before the inputs
    assert kept_products(spec_of(tmp_path, "looped"), 1, 128, None) == (
        "mlp.down", "mixer.o", "mixer.q", "mixer.k", "mixer.v",
        "mlp.gate", "mlp.up")


@pytest.mark.parametrize("name", ["hybrid", "looped"])
def test_full_partial_and_empty_sets_at_three_budgets(tmp_path, name):
    spec = spec_of(tmp_path, name)
    full = kept_products(spec, 2, 128, None)
    size = lambda kept: kept_counters(spec, 128, kept)[
        "lm_kept_residual_bytes"] * 2           # two rows
    assert kept_products(spec, 2, 128, int(size(full))) == full
    assert kept_products(spec, 2, 128, 0) == ()
    budget = int(size(full[:2])) + 1
    partial = kept_products(spec, 2, 128, budget)
    # down the list: the first two, then whatever smaller one still fits
    assert partial[:2] == full[:2] and set(partial) < set(full)
    assert size(partial) <= budget
    assert all(size((n,)) > budget - size(partial)
               for n in full if n not in partial)
    # the same answer for the same shapes; twice the rows need twice
    assert kept_products(spec, 2, 128, budget) == partial
    assert kept_products(spec, 4, 128, 2 * budget) == partial
    share = kept_counters(spec, 128, partial)["lm_kept_product_share"]
    assert 0.0 < share < 1.0
    assert kept_counters(spec, 128, full)["lm_kept_product_share"] == 1.0
    assert kept_counters(spec, 128, ()) == {
        "lm_kept_product_share": 0.0, "lm_kept_residual_bytes": 0.0}


@pytest.mark.parametrize("name", ["hybrid", "looped"])
def test_all_is_kept_where_the_backend_reports_no_memory(tmp_path, name):
    """The CPU's ``memory_stats()`` is None: no limit known."""
    spec = spec_of(tmp_path, name)
    assert jax.local_devices()[0].memory_stats() is None
    assert residual_budget(spec, 1, 128, None) is None
    assert residual_budget(spec, 1, 128, {}) is None
    hybrid_lm._kept_for.cache_clear()
    full = kept_products(spec, 1, 128, None)
    assert hybrid_lm._kept_for(spec, 1, 128) == full
    model = HybridLM("hybrid_lm", spec, dtype="bfloat16",
                     attention="auto", remat=True)
    assert kept_counters(spec, 128, full).items() \
        <= model.trace_gauges(1, 128).items()
    # a device that is full keeps nothing
    assert residual_budget(spec, 1, 128, {
        "bytes_limit": GIB, "bytes_in_use": GIB}) == 0


# the chip's allocator as the two cells' rounds are traced (TPU v5e,
# one chip; my chip runs, PR 38): the limit, and what the server's tree
# and the clients' store hold (the store's bytes do not change with
# the rows' length: fewer rows of more tokens)
V5E_LIMIT = 16909336064
CELLS = {
    "olmo_hybrid_7b_l4": dict(
        in_use=4804472320, tokens=2048, longer=4096,
        at_longer=()),
    "ouro_2_6b_l8": dict(
        in_use=4613676032, tokens=1024, longer=2048,
        at_longer=("mlp.down", "mixer.o")),
}


@pytest.mark.parametrize("config", sorted(CELLS))
def test_the_cells_sets_at_their_published_widths(config):
    """At the cell's length every product is kept (PERF.md section 5:
    1.39 GiB a sequence in the olmo cell, 2.62 in the looped one); at
    the length its issue first asked for, a strict subset (nothing in
    the olmo cell, whose round at 4096 tokens leaves no room by this
    reckoning) that was run on the chip (section 6, PR 38)."""
    cell = CELLS[config]
    spec = load_spec(f"benchmark/configs/{config}.json")
    stats = {"bytes_limit": V5E_LIMIT, "bytes_in_use": cell["in_use"]}
    full = kept_products(spec, 1, cell["tokens"], None)

    def chosen(tokens):
        return kept_products(spec, 1, tokens,
                             residual_budget(spec, 1, tokens, stats))

    assert chosen(cell["tokens"]) == full
    assert kept_counters(spec, cell["tokens"], full)[
        "lm_kept_residual_bytes"] == {
            "olmo_hybrid_7b_l4": 1493598208, "ouro_2_6b_l8": 2818572288
        }[config]
    assert chosen(cell["longer"]) == cell["at_longer"]
    assert set(cell["at_longer"]) < set(full)
    share = kept_counters(spec, cell["longer"], cell["at_longer"])[
        "lm_kept_product_share"]
    assert share == pytest.approx(
        {"olmo_hybrid_7b_l4": 0.0, "ouro_2_6b_l8": 15 / 49}[config])
    # the longest inner dimension leads at every length
    assert full[0] == "mlp.down"
    # a device with a sixteenth of the memory left keeps nothing
    assert kept_products(spec, 1, cell["tokens"], residual_budget(
        spec, 1, cell["tokens"],
        {"bytes_limit": V5E_LIMIT,
         "bytes_in_use": V5E_LIMIT - (1 << 30)})) == ()
