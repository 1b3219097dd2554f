"""``reference/ouro.py``'s own arithmetic against a two-pass case
unrolled by hand in numpy float64: one layer, two passes, two heads,
five tokens, every sum and every rotation written as a loop."""
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import _ops, ouro

SPEC = {"num_hidden_layers": 1, "num_attention_heads": 2,
        "rms_norm_eps": 1e-6, "rope_theta": 100.0, "total_ut_steps": 2,
        "exit_entropy_beta": 0.05}
D, F, V, T = 8, 12, 11, 5


def seeded_params():
    rng = np.random.RandomState(5)
    mat = lambda *shape: rng.randn(*shape) * 0.3
    scale = lambda: 1.0 + 0.1 * rng.randn(D)
    return {
        "embed": mat(V, D), "head": mat(D, V), "final_norm": scale(),
        "exit_gate": {"w": mat(D), "b": np.asarray([0.2])},
        "layer_0": {
            "mixer": {n: mat(D, D) for n in ("wq", "wk", "wv", "wo")},
            "mlp": {"gate": mat(D, F), "up": mat(D, F), "down": mat(F, D)},
            "mixer_in_norm": scale(), "mixer_norm": scale(),
            "mlp_in_norm": scale(), "mlp_norm": scale()}}


def norm(v, scale):
    return v / math.sqrt(np.mean(v * v) + 1e-6) * scale


def turn(v, pos, theta):
    """One head's vector at position ``pos``: pair (i, i + d/2) turned
    by ``pos * theta^(-2i/d)``."""
    d, out = len(v), np.array(v)
    for i in range(d // 2):
        a = pos * theta ** (-2.0 * i / d)
        out[i] = v[i] * math.cos(a) - v[i + d // 2] * math.sin(a)
        out[i + d // 2] = v[i + d // 2] * math.cos(a) + v[i] * math.sin(a)
    return out


def layer_by_hand(p, x):
    """``x``: [T, D] -> [T, D]."""
    H, hd = 2, D // 2
    u = np.stack([norm(x[t], p["mixer_in_norm"]) for t in range(T)])
    q, k, v = (u @ p["mixer"][n] for n in ("wq", "wk", "wv"))
    mixed = np.zeros((T, D))
    for h in range(H):
        sl = slice(h * hd, (h + 1) * hd)
        qh = [turn(q[t, sl], t, 100.0) for t in range(T)]
        kh = [turn(k[t, sl], t, 100.0) for t in range(T)]
        for t in range(T):
            w = np.array([math.exp(qh[t] @ kh[j] / math.sqrt(hd))
                          for j in range(t + 1)])
            w /= w.sum()
            mixed[t, sl] = sum(w[j] * v[j, sl] for j in range(t + 1))
    out = mixed @ p["mixer"]["wo"]
    a = np.stack([x[t] + norm(out[t], p["mixer_norm"]) for t in range(T)])
    u = np.stack([norm(a[t], p["mlp_in_norm"]) for t in range(T)])
    gate = u @ p["mlp"]["gate"]
    m = (gate / (1.0 + np.exp(-gate)) * (u @ p["mlp"]["up"])) \
        @ p["mlp"]["down"]
    return np.stack([a[t] + norm(m[t], p["mlp_norm"]) for t in range(T)])


def objective_by_hand(p, ids):
    h = p["embed"][ids]
    ce, lam = [], []
    for _ in range(2):
        h = layer_by_hand(p["layer_0"], h)
        h = np.stack([norm(h[t], p["final_norm"]) for t in range(T)])
        logits = h @ p["head"]
        ce.append([math.log(np.exp(logits[t]).sum()) - logits[t, ids[t + 1]]
                   for t in range(T - 1)])
        z = h @ p["exit_gate"]["w"] + p["exit_gate"]["b"][0]
        lam.append(1.0 / (1.0 + np.exp(-z[:-1])))
    q = [lam[0], 1.0 - lam[0]]              # two passes: the rest exits last
    per = [q[0][t] * ce[0][t] + q[1][t] * ce[1][t]
           + 0.05 * sum(q[i][t] * math.log(q[i][t]) for i in range(2))
           for t in range(T - 1)]
    return np.mean(per), [np.mean(c) for c in ce], [np.mean(x) for x in q]


def test_two_passes_unrolled_by_hand():
    params = seeded_params()
    ids = np.asarray([3, 7, 0, 10, 7])
    want, want_ce, want_q = objective_by_hand(params, ids)
    as32 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        loss, ce, q = ouro.objective(as32, jnp.asarray(ids)[None], SPEC)
        same = ouro.make_loss(SPEC)(as32, jnp.asarray(ids)[None], None)
    np.testing.assert_allclose(loss, want, rtol=2e-6)
    np.testing.assert_allclose(ce, want_ce, rtol=2e-6)
    np.testing.assert_allclose(q, want_q, rtol=2e-6)
    assert float(same) == float(loss)
    assert abs(sum(want_q) - 1.0) < 1e-12


def test_the_control_hook_reaches_every_product():
    """Rounding the operands of every product moves the loss; the
    pointwise parts are not behind the hook, so identity leaves it."""
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                          seeded_params())
    x = jnp.asarray([[3, 7, 0, 10, 7]])
    loss = ouro.make_loss(SPEC)
    plain = float(loss(params, x, None))
    assert float(loss(params, x, None, _ops.identity)) == plain
    rounded = float(loss(params, x, None, _ops.bf16_round_trip))
    assert 1e-6 < abs(rounded - plain) / plain < 0.05


def test_specification_is_read_from_the_configurations_file():
    s = ouro.load_spec()
    assert (s["num_hidden_layers"], s["total_ut_steps"],
            s["num_attention_heads"], s["rope_theta"],
            s["exit_entropy_beta"]) == (8, 4, 16, 1000000, 0.05)
