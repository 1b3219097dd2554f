"""Fixture-driven tests for the tracing-hazard analyzer.

Each rule gets positive controls (the hazard, asserted by exact rule
id AND line number) and negative controls (the legal idiom the rule
must NOT flag) — including the two the issue calls out explicitly:
numpy at setup time, and key reuse after an intervening fold_in.
"""
import textwrap

import pytest

from fedtorch_tpu.lint import analyze_source
from fedtorch_tpu.lint.findings import (
    diff_against_baseline, load_baseline, save_baseline,
    suppressions_for_source,
)


def hits(src, rule=None):
    """[(rule, line)] findings for a dedented source snippet."""
    out = analyze_source(textwrap.dedent(src), "snippet.py")
    if rule is not None:
        out = [f for f in out if f.rule == rule]
    return [(f.rule, f.line) for f in out]


# -- FTL001: host syncs -----------------------------------------------------

def test_ftl001_float_on_jnp_expr():
    src = """\
    import jax.numpy as jnp

    def round_metrics(losses):
        a = float(jnp.sum(losses))
        b = int(jnp.argmax(losses))
        c = bool(jnp.all(losses > 0))
        return a, b, c
    """
    assert hits(src, "FTL001") == [("FTL001", 4), ("FTL001", 5),
                                   ("FTL001", 6)]


def test_ftl001_item_and_np_asarray():
    src = """\
    import numpy as np
    import jax.numpy as jnp

    def log_round(metrics):
        loss = jnp.mean(metrics)
        x = loss.item()
        y = np.asarray(jnp.exp(loss))
        return x, y
    """
    assert hits(src, "FTL001") == [("FTL001", 6), ("FTL001", 7)]


def test_ftl001_from_import_numpy_member():
    """`from numpy import asarray` must canonicalize like np.asarray —
    the bare-name alias is a real detection surface, not dead code."""
    src = """\
    import jax.numpy as jnp
    from numpy import asarray

    def fetch(metrics):
        return asarray(jnp.sum(metrics))
    """
    assert hits(src, "FTL001") == [("FTL001", 5)]


def test_ftl001_negative_host_values():
    """float() on plain host math and on device_get results is legal —
    device_get is the sanctioned batched-transfer idiom."""
    src = """\
    import jax
    import jax.numpy as jnp

    def fine(sizes, metrics):
        n = float(sum(sizes))
        host = jax.device_get({"m": jnp.mean(metrics)})
        return n + float(host["m"])
    """
    assert hits(src, "FTL001") == []


@pytest.mark.parametrize("method", ["round_host_fetch",
                                    "round_host_scalars"])
def test_ftl001_negative_trainer_host_fetch(method):
    """The trainer's batched fetches hand back host values whatever
    device state they are handed; the same call under another name is
    still a device value."""
    src = f"""\
    import jax.numpy as jnp

    def loop(trainer, state):
        clients = jnp.zeros((4,)) + state
        got = trainer.{method}(clients, None)
        return float(got[0])
    """
    assert hits(src, "FTL001") == []
    assert hits(src.replace(method, "round_dev"), "FTL001") \
        == [("FTL001", 6)]


def test_ftl001_inside_jit_is_flagged():
    src = """\
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        s = jnp.sum(x)
        return x / float(s)
    """
    assert hits(src, "FTL001") == [("FTL001", 7)]


# -- FTL002: numpy inside traced code ---------------------------------------

def test_ftl002_numpy_on_traced_value():
    src = """\
    import jax
    import numpy as np

    @jax.jit
    def bad(x, w):
        return np.dot(x, w)
    """
    assert hits(src, "FTL002") == [("FTL002", 6)]


def test_ftl002_negative_numpy_at_setup_time():
    """numpy on host data outside traced code is the LEGAL setup-time
    pattern (15 modules import numpy for exactly this)."""
    src = """\
    import numpy as np

    def build_batches(x, batch_size):
        n = np.ceil(len(x) / batch_size)
        perm = np.random.permutation(len(x))
        return np.split(x[perm], int(n))
    """
    assert hits(src, "FTL002") == []


def test_ftl002_negative_numpy_constant_inside_jit():
    """numpy math on static host constants inside jit traces to a
    constant on purpose (shape/eps math) — not flagged."""
    src = """\
    import jax
    import numpy as np

    @jax.jit
    def ok(x):
        eps = np.sqrt(2.0)
        return x * eps
    """
    assert hits(src, "FTL002") == []


def test_ftl002_reachable_from_jit():
    """Reachability: a helper called from a jitted function is traced
    even without its own decorator (intra-module closure)."""
    src = """\
    import jax
    import numpy as np

    def helper(x):
        return np.square(x)

    @jax.jit
    def outer(x):
        return helper(x)
    """
    assert hits(src, "FTL002") == [("FTL002", 5)]


# -- FTL003: PRNG discipline ------------------------------------------------

def test_ftl003_key_reuse():
    src = """\
    import jax

    def sample(key):
        a = jax.random.normal(key, (3,))
        b = jax.random.uniform(key, (3,))
        return a + b
    """
    assert hits(src, "FTL003") == [("FTL003", 5)]


def test_ftl003_negative_split_and_fold_in():
    """The two sanctioned refresh idioms: split into distinct keys,
    and rebinding through fold_in before the next consumption."""
    src = """\
    import jax

    def sample(key):
        k1, k2 = jax.random.split(key)
        a = jax.random.normal(k1, (3,))
        b = jax.random.uniform(k2, (3,))
        key = jax.random.fold_in(key, 7)
        c = jax.random.normal(key, (3,))
        key = jax.random.fold_in(key, 8)
        d = jax.random.normal(key, (3,))
        return a + b + c + d
    """
    assert hits(src, "FTL003") == []


def test_ftl003_loop_reuse():
    """A key bound outside a loop and consumed each iteration draws
    the SAME stream every pass — the silent determinism killer."""
    src = """\
    import jax

    def rounds(key, n):
        out = []
        for i in range(n):
            out.append(jax.random.normal(key, (2,)))
        return out
    """
    assert hits(src, "FTL003") == [("FTL003", 6)]


def test_ftl003_negative_fold_in_inside_loop():
    src = """\
    import jax

    def rounds(key, n):
        out = []
        for i in range(n):
            k = jax.random.fold_in(key, i)
            out.append(jax.random.normal(k, (2,)))
        return out
    """
    assert hits(src, "FTL003") == []


def test_ftl003_negative_exclusive_branches():
    """Mutually exclusive branches each consume the key once — only
    one ever runs, so this is NOT reuse (branch-local state copies
    must be deep: the per-key dicts are mutated in place)."""
    src = """\
    import jax

    def sample(key, gaussian):
        if gaussian:
            x = jax.random.normal(key, (3,))
        else:
            x = jax.random.uniform(key, (3,))
        return x
    """
    assert hits(src, "FTL003") == []


def test_ftl003_negative_split_iteration():
    """Iterating over split keys consumes a fresh key per pass."""
    src = """\
    import jax

    def batch(key, n):
        out = []
        for k in jax.random.split(key, n):
            out.append(jax.random.normal(k, (2,)))
        return out
    """
    assert hits(src, "FTL003") == []


# -- FTL004: missing donation ------------------------------------------------

def test_ftl004_rebuild_without_donation():
    src = """\
    import jax
    import jax.numpy as jnp

    def train_step(params, grads):
        new_params = jax.tree.map(lambda p, g: p - 0.1 * g,
                                  params, grads)
        return new_params

    step = jax.jit(train_step)
    """
    assert hits(src, "FTL004") == [("FTL004", 9)]


def test_ftl004_negative_with_donation():
    src = """\
    import jax
    import jax.numpy as jnp

    def train_step(params, grads):
        return jax.tree.map(lambda p, g: p - 0.1 * g, params, grads)

    step = jax.jit(train_step, donate_argnums=(0,))
    """
    assert hits(src, "FTL004") == []


def test_ftl004_negative_scalar_output():
    """Functions returning fresh reductions (not rebuilt inputs) are
    not donation candidates."""
    src = """\
    import jax
    import jax.numpy as jnp

    @jax.jit
    def loss(params):
        return jnp.float32(0.0)
    """
    assert hits(src, "FTL004") == []


# -- FTL005: branching on traced values --------------------------------------

def test_ftl005_if_on_traced_value():
    src = """\
    import jax
    import jax.numpy as jnp

    @jax.jit
    def clip(x):
        if jnp.max(x) > 1.0:
            return x / jnp.max(x)
        return x
    """
    assert hits(src, "FTL005") == [("FTL005", 6)]


def test_ftl005_host_coercion_branch():
    src = """\
    import jax.numpy as jnp

    def supervise(loss_history):
        if float(jnp.mean(loss_history)) > 10.0:
            return "rollback"
        return "ok"
    """
    assert hits(src, "FTL005") == [("FTL005", 4)]
    # the coercion inside the claimed test is NOT double-reported
    assert hits(src, "FTL001") == []


def test_ftl005_negative_static_branches():
    """Static config flags, shape metadata, and None checks are the
    legal Python branches traced code is built from."""
    src = """\
    import jax
    import jax.numpy as jnp

    @jax.jit
    def fwd(x, w, mask=None):
        if x.ndim == 3:
            x = x.reshape(-1, x.shape[-1])
        if mask is not None:
            x = x * mask
        if isinstance(w, dict):
            w = w["kernel"]
        return jnp.dot(x, w)
    """
    assert hits(src, "FTL005") == []


# -- suppressions & baseline -------------------------------------------------

def test_suppression_requires_justification():
    src = """\
    import jax.numpy as jnp

    def a(x):
        return float(jnp.sum(x))  # lint: disable=FTL001

    def b(x):
        # lint: disable=FTL001 — one-shot setup scalar, not per-round
        return float(jnp.sum(x))
    """
    # bare disable is inert (a); justified disable suppresses (b)
    assert hits(src, "FTL001") == [("FTL001", 4)]


def test_suppression_parsing():
    by_line = suppressions_for_source(
        "x = 1  # lint: disable=FTL001,FTL005 — measured, accepted\n")
    assert by_line[1] == {"FTL001", "FTL005"}
    assert by_line[2] == {"FTL001", "FTL005"}  # covers the line below


def test_baseline_roundtrip(tmp_path):
    src = textwrap.dedent("""\
    import jax.numpy as jnp

    def a(x):
        return float(jnp.sum(x))
    """)
    findings = analyze_source(src, "mod.py")
    assert len(findings) == 1
    path = tmp_path / "baseline.json"
    save_baseline(str(path), findings)
    base = load_baseline(str(path))
    new, matched = diff_against_baseline(findings, base)
    assert new == [] and matched == 1
    # fingerprints are line-number independent: shifting the module
    # down two lines must not produce a "new" finding
    shifted = analyze_source("\n\n" + src, "mod.py")
    new2, _ = diff_against_baseline(shifted, base)
    assert new2 == []


# -- traced-context discovery: scan/while bodies as LOCAL CLOSURES ----------
# The PR 11 round_program.py idiom: the loop body is built by a
# factory / bound to a local name before the tracing call. Direct and
# partial decoration and direct call-site passing were always modeled;
# these fixtures pin the binding-resolution extension (ISSUE 13).

def test_scan_body_from_closure_factory_bound_to_local():
    """`step = _make_body(t)` then `lax.scan(step, ...)` — the factory
    RESULT is the traced body, reached through the binding map."""
    src = """\
    import jax
    import numpy as np

    def _make_body(c):
        def body(carry, x):
            v = np.sqrt(carry)
            return carry + v * c, x
        return body

    def driver(init, xs):
        step = _make_body(2.0)
        return jax.lax.scan(step, init, xs)
    """
    assert hits(src, "FTL002") == [("FTL002", 6)]


def test_while_loop_bodies_as_name_assigned_lambdas():
    src = """\
    import jax
    import numpy as np

    def run(x):
        body = lambda s: (s[0] + np.exp(s[0]), s[1] + 1)
        cond = lambda s: s[1] < 4
        return jax.lax.while_loop(cond, body, (x, 0))
    """
    assert hits(src, "FTL002") == [("FTL002", 5)]


def test_scan_body_rebound_conditionally():
    """`fn = a_body if flag else b_body` — both candidates trace."""
    src = """\
    import jax
    import numpy as np

    def a_body(c, x):
        return c + np.log(c), x

    def b_body(c, x):
        return c * 2, x

    def driver(init, xs, flag):
        fn = a_body if flag else b_body
        return jax.lax.scan(fn, init, xs)
    """
    assert hits(src, "FTL002") == [("FTL002", 5)]


def test_factory_returning_call_result_is_not_traced():
    """Negative control for the binding resolution: a helper that
    returns a CALL RESULT (not a function) must not mark itself or
    its callees traced — `params = run_ascent(...)` is data flow, not
    closure passing (the over-binding that would cascade false
    FTL005s through the intra-module call graph)."""
    src = """\
    import jax
    import jax.numpy as jnp
    import numpy as np

    def run_ascent(params, xs):
        if len(xs) > 2:
            return params
        return params

    def driver(params, xs):
        params = run_ascent(params, xs)
        return jax.lax.scan(lambda c, x: (c, x), params, xs)
    """
    assert hits(src) == []


def test_traced_lambda_params_are_device_flavored():
    """A name-assigned lambda marked traced treats its parameters as
    device values, so in-body hazards (host coercions) are caught."""
    src = """\
    import jax

    def run(x):
        body = lambda s: (s[0] + float(s[0]), s[1] + 1)
        cond = lambda s: s[1] < 4
        return jax.lax.while_loop(cond, body, (x, 0))
    """
    assert hits(src, "FTL001") == [("FTL001", 4)]
