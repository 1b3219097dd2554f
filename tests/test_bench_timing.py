"""Pin the fetch-synced timer (scripts/bench_timing.py) every
micro-benchmark depends on: sync() must materialize real bytes for
any result shape, and timeit() must return a sane per-call mean."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest

# load the script module without mutating sys.path: a path insert
# would shadow any test-session import that collides with a scripts/
# filename
_spec = importlib.util.spec_from_file_location(
    "bench_timing", os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts", "bench_timing.py"))
_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mod)
sync, timeit = _mod.sync, _mod.timeit
timeit_crosscheck = _mod.timeit_crosscheck


class TestSync:
    def test_array(self):
        out = jnp.arange(12.0).reshape(3, 4)
        assert float(sync(out)) == 0.0

    def test_scalar(self):
        # ndim-0 leaf: the (0,)*0 == () index path
        assert float(sync(jnp.float32(7.0))) == 7.0

    def test_pytree(self):
        tree = {"a": (jnp.ones((2, 2)), jnp.zeros(3))}
        assert float(sync(tree)) == 1.0  # first leaf

    def test_grad_tuple(self):
        # the block-sweep fwd+bwd shape: a tuple of grads
        g = jax.grad(lambda q, k: jnp.sum(q ** 2 + k), argnums=(0, 1))(
            jnp.ones(4), jnp.ones(4))
        assert float(sync(g)) == 2.0


class TestTimeit:
    def test_returns_positive_mean(self):
        f = jax.jit(lambda x: x @ x)
        x = jnp.ones((64, 64))
        t = timeit(f, x, iters=3)
        assert t > 0

    def test_actually_calls_iters_times(self):
        calls = []

        def f(x):
            calls.append(1)
            return x + 1

        timeit(f, jnp.ones(4), iters=5)
        assert len(calls) == 6  # warmup + iters

    def test_sync_each_mode_calls_and_drains(self):
        """The opt-in per-iteration-sync cross-check mode: same call
        count, every iteration drained through a
        fetch before the next dispatch."""
        calls = []

        def f(x):
            calls.append(1)
            return x + 1

        t = timeit(f, jnp.ones(4), iters=5, sync_each=True)
        assert t > 0 and len(calls) == 6


class TestTimeitCrosscheck:
    def test_honest_backend_not_suspicious(self):
        """On a backend that really executes queued work (the CPU
        mesh), synced-vs-queued stays within the fetch-latency band —
        far from the 3x suspicion threshold."""
        f = jax.jit(lambda x: (x @ x).sum())
        x = jnp.ones((128, 128))
        r = timeit_crosscheck(f, x, iters=10)
        assert set(r) == {"queued_s", "synced_s",
                          "sync_overhead_ratio", "suspect_ratio",
                          "suspicious"}
        assert r["queued_s"] > 0 and r["synced_s"] > 0
        assert r["sync_overhead_ratio"] == pytest.approx(
            r["synced_s"] / r["queued_s"])

    def test_suspicion_threshold_flags(self):
        """Positive control: with the threshold dialed below the
        measured ratio, the same reading flags as suspicious — the
        ack-without-execute signature detector fires."""
        f = jax.jit(lambda x: (x @ x).sum())
        x = jnp.ones((64, 64))
        honest = timeit_crosscheck(f, x, iters=5,
                                   suspect_ratio=1e9)
        assert honest["suspicious"] is False
        rigged = timeit_crosscheck(f, x, iters=5, suspect_ratio=0.0)
        assert rigged["suspicious"] is True
