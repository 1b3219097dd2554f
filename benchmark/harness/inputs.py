"""What the round program fed the model, rebuilt outside it.

The reference is given the cohort and the batches of a round as inputs.
They are a function of the run's key and the round's index alone, so
the harness rebuilds them after the window with the program's own input
helpers: the cohort draw (``parallel/federated.participation_indices``),
the round's row plan (``data/batching.round_row_plan``) and the CIFAR
flip-and-crop (``ops/augment.augment_image_batch``), on the key chain
``parallel/federated.FederatedTrainer.round_fn`` folds. Cohort sampling
and augmentation therefore stay with the repo's own parity tests; what
the reference checks is everything after the batches exist.
"""
from __future__ import annotations


def seed_keys(trainer, manual_seed: int):
    """(server key, initial parameters): the two lines of
    ``FederatedTrainer.init_state`` that make them from the seed."""
    import jax

    rng, init_rng = jax.random.split(jax.random.key(manual_seed))
    return rng, trainer.model.init(init_rng)


def _draw(trainer):
    """``(key, round) -> (cohort [k], the round's training key)`` on the
    key chain ``FederatedTrainer.round_fn`` folds."""
    import jax

    from fedtorch_tpu.parallel.federated import participation_indices

    C, k = int(trainer.num_clients), int(trainer.k_dispatch)
    mode = trainer.participation_mode

    def draw(rng, r):
        rng_sample, rng_train = jax.random.split(jax.random.fold_in(rng, r))
        return participation_indices(rng_sample, C, k, r, mode=mode), \
            rng_train
    return draw


def cohort_of(trainer, server_rng, round_idx: int):
    """The client ids of one round's cohort, as a list."""
    import jax
    import jax.numpy as jnp

    idx, _ = jax.jit(_draw(trainer))(server_rng,
                                     jnp.asarray(round_idx, jnp.int32))
    return jax.device_get(idx).tolist()


def round_inputs(trainer, server_rng, round_idx: int):
    """(cohort [k], x [k, K, B, ...], y [k, K, B]) of one round, as
    numpy arrays on the host."""
    import jax
    import jax.numpy as jnp

    from fedtorch_tpu.data.batching import round_row_plan
    from fedtorch_tpu.ops.augment import augment_image_batch

    if trainer.gather_mode != "batch" or trainer.data is None:
        raise NotImplementedError(
            "inputs are rebuilt for the device-resident 'batch' gather")
    K, B = int(trainer.local_steps), int(trainer.batch_size)
    k, augment = int(trainer.k_dispatch), bool(trainer.augment)
    draw = _draw(trainer)

    def plan(sizes_all, rng, r):
        idx, rng_train = draw(rng, r)
        sizes = jnp.take(sizes_all, idx)
        rngs = jax.random.split(rng_train, k)
        rows = jax.vmap(lambda c, s: round_row_plan(
            c, s, n_max, K * B))(rngs, sizes)
        return idx, rows, rngs

    def augmented(x, rngs):
        def client(rng_c, xc):
            parent = jax.random.fold_in(rng_c, 0x7FFFFFFF)
            return jax.vmap(lambda s, xb: augment_image_batch(
                jax.random.fold_in(parent, s), xb))(jnp.arange(K), xc)
        return jax.vmap(client)(rngs, x)

    data = trainer.data
    n_max = int(data.x.shape[1])
    idx, rows, rngs = jax.jit(plan)(data.sizes, server_rng,
                                    jnp.asarray(round_idx, jnp.int32))
    # one client's shard at a time, rows taken from its flat [n, pixels]
    # view: a gather over the whole 5-D store makes the compiler copy
    # the store into a padded layout (4x its size on the chip)
    xs, ys = [], []
    for j, c in enumerate(jax.device_get(idx).tolist()):
        shard = data.x[c]
        flat = shard.reshape((n_max, -1))
        xs.append(jnp.take(flat, rows[j], axis=0).reshape(
            (K, B) + shard.shape[1:]))
        ys.append(jnp.take(data.y[c], rows[j], axis=0).reshape((K, B)))
    x, y = jnp.stack(xs), jnp.stack(ys)
    if augment:
        x = jax.jit(augmented)(x, rngs)
    return jax.device_get((idx, x, y))
