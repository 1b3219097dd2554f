"""``reference/fedavg.py``'s round, which keeps at most three
model-sized float32 trees on the device, against the round it replaced,
written out here as the oracle (server, sum, client and gradient all on
the device): bit-identical under each of the control's three hooks, and
a count of what is live on the device at every step and fold.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import _ops, fedavg

K_COHORT, K_STEPS, BATCH, WIDTH, DEPTH = 3, 2, 8, 64, 6
HP = {"lr": 0.1, "weight_decay": 1e-4, "server_lr": 1.0}


def loss(params, x, y, cast):
    """A small dense model: DEPTH hidden layers and a softmax loss."""
    for w, b in params["hidden"]:
        x = jax.nn.relu(_ops.dense(x, w, b, cast))
    return _ops.softmax_cross_entropy(
        _ops.dense(x, params["out"]["w"], params["out"]["b"], cast), y)


def seeded(seed):
    rng = np.random.default_rng(seed)
    f = lambda *s: rng.standard_normal(s).astype(np.float32)
    params = {"hidden": [(0.2 * f(WIDTH, WIDTH), 0.1 * f(WIDTH))
                         for _ in range(DEPTH)],
              "out": {"w": 0.2 * f(WIDTH, 10), "b": 0.1 * f(10)}}
    xs = f(K_COHORT, K_STEPS, BATCH, WIDTH)
    ys = rng.integers(0, 10, (K_COHORT, K_STEPS, BATCH)).astype(np.int32)
    return params, xs, ys


# -- the oracle: reference/fedavg.py as PR 23 wrote it ---------------------

def old_round(loss_fn, hp, cast, param_cast, accum_cast):
    def step(params, x, y):
        value, grads = jax.value_and_grad(loss_fn)(params, x, y, cast)
        new = jax.tree.map(
            lambda p, g: param_cast(p - hp["lr"] * (
                g + hp["weight_decay"] * p)), params, grads)
        return new, value
    step = jax.jit(step)

    def run_round(server, state, cohort, xs, ys):
        w = fedavg.cohort_weight(cohort)
        total = jax.tree.map(jnp.zeros_like, server)
        losses = []
        for c in range(len(cohort)):
            params, client_losses = server, []
            for s in range(xs.shape[1]):
                params, value = step(params, xs[c, s], ys[c, s])
                client_losses.append(value)
            losses.append(jnp.mean(jnp.stack(client_losses)))
            total = jax.tree.map(
                lambda t, sp, p: accum_cast(t + accum_cast(w * (sp - p))),
                total, server, params)
        new_server = jax.tree.map(
            lambda sp, t: param_cast(sp - hp["server_lr"] * t), server,
            total)
        return new_server, state, jnp.mean(jnp.stack(losses))
    return run_round


HOOKS = {"none": {}, "fp8": dict(cast=_ops.fp8_round_trip),
         "bf16_params": dict(param_cast=_ops.bf16_round_trip),
         "bf16_accum": dict(accum_cast=_ops.bf16_round_trip)}


def hooks(name):
    return {k: HOOKS[name].get(k, _ops.identity)
            for k in ("cast", "param_cast", "accum_cast")}


@pytest.mark.parametrize("cohort", [(0, 4, 7), (2, 4, 7)])
@pytest.mark.parametrize("name", list(HOOKS))
def test_round_is_bit_identical_to_the_one_it_replaced(name, cohort):
    p0, xs, ys = seeded(11)
    h = hooks(name)
    with jax.default_matmul_precision("highest"):
        old = old_round(loss, HP, **h)
        new = fedavg.make_round(loss, HP, **h)
        # as the old run_reference started the old round: cast on the device
        want = jax.tree.map(
            lambda x: h["param_cast"](jnp.asarray(x, jnp.float32)), p0)
        got = p0
        for r in range(2):      # the second round starts from the first's
            want, _, want_loss = old(want, None, cohort, jnp.asarray(xs),
                                     jnp.asarray(ys))
            got, state, got_loss = new(got, None, cohort, jnp.asarray(xs),
                                       jnp.asarray(ys))
            assert state is None
            assert float(got_loss) == float(want_loss)
            assert jax.tree.structure(got) == jax.tree.structure(p0)
            for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                assert isinstance(g, np.ndarray) and g.dtype == np.float32
                np.testing.assert_array_equal(g, np.asarray(w),
                                              err_msg=f"{name} round {r}")
    # the round moved every leaf, and the hook is in it
    moved = list(zip(jax.tree.leaves(got), jax.tree.leaves(p0)))
    assert all(np.any(g != z) for g, z in moved)
    if name == "bf16_params":
        assert all(np.array_equal(g, np.asarray(
            _ops.bf16_round_trip(jnp.asarray(g)))) for g, _ in moved)


def test_at_most_three_trees_on_the_device(monkeypatch):
    """What is live on the device after every local step and at every
    leaf's fold: the running sum, the client's parameters and (at a
    fold) the server's, never a fourth tree. The gradient lives inside
    the step, whose parameters are donated."""
    p0, xs, ys = seeded(12)
    xs_d, ys_d = jnp.asarray(xs), jnp.asarray(ys)
    sizes = [v.nbytes for v in jax.tree.leaves(p0)]
    tree_bytes, largest = sum(sizes), max(sizes)
    fixed = sum(a.nbytes for a in jax.live_arrays())
    seen = {"step": [], "fold": []}

    def live(where):
        seen[where].append(
            sum(a.nbytes for a in jax.live_arrays()) - fixed)

    real = fedavg.make_local_step

    def watched(*args):
        step = real(*args)

        def one(params, x, y):
            given = jax.tree.leaves(params)
            out = jax.block_until_ready(step(params, x, y))
            assert all(a.is_deleted() for a in given)   # donated
            live("step")
            return out
        return one

    def accum_cast(x):
        live("fold")
        return x

    monkeypatch.setattr(fedavg, "make_local_step", watched)
    run_round = fedavg.make_round(loss, HP, _ops.identity, _ops.identity,
                                  accum_cast)
    run_round(p0, None, (0, 1, 2), xs_d, ys_d)
    assert len(seen["step"]) == K_COHORT * K_STEPS
    assert len(seen["fold"]) == K_COHORT * 2 * len(sizes)
    slack = 8192        # the steps' batches and losses, a twelfth of a tree
    # between steps: the sum and the client's parameters; at a fold:
    # those (the server's leaf takes the client's place) and one
    # leaf's temporaries, which is under three trees
    assert max(seen["step"]) <= 2 * tree_bytes + slack
    assert 2 * tree_bytes < max(seen["fold"]) \
        <= 2 * tree_bytes + 4 * largest + slack < 3 * tree_bytes
