"""``reference/kanana2.py``'s own arithmetic against a case written out
by hand in numpy float64: a dense layer and an expert layer, two heads
of 2 + 2 on value heads of 3, a latent of 3 + 2, four routed experts of
which the chip holds the last two, two a token, a shared expert, four
tokens; every sum, rotation, choice and gate written as a loop."""
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import _ops, kanana2

SPEC = {"num_hidden_layers": 2, "num_attention_heads": 2,
        "rms_norm_eps": 1e-6, "rope_theta": 100.0, "kv_lora_rank": 3,
        "qk_nope_head_dim": 2, "qk_rope_head_dim": 2, "v_head_dim": 3,
        "first_k_dense_replace": 1, "num_experts_per_tok": 2,
        "norm_topk_prob": True, "routed_scaling_factor": 2.448,
        "routed_experts": 4, "first_expert_held": 2,
        "balance_loss_coef": 0.05}
D, F, FD, V, T, H, DN, DR, DV, R = 6, 5, 7, 9, 4, 2, 2, 2, 3, 3


def seeded_params():
    rng = np.random.RandomState(11)
    mat = lambda *shape: rng.randn(*shape) * 0.4
    scale = lambda n: 1.0 + 0.1 * rng.randn(n)
    mixer = lambda: {"wq": mat(D, H * (DN + DR)), "wkv_a": mat(D, R + DR),
                     "kv_a_norm": scale(R), "wkv_b": mat(R, H * (DN + DV)),
                     "wo": mat(H * DV, D)}
    swiglu = lambda f: {"gate": mat(D, f), "up": mat(D, f),
                        "down": mat(f, D)}
    return {
        "embed": mat(V, D), "head": mat(D, V), "final_norm": scale(D),
        "layer_0": {"mixer": mixer(), "mlp": swiglu(FD),
                    "mixer_norm": scale(D), "mlp_norm": scale(D)},
        "layer_1": {"mixer": mixer(),
                    "mlp": {"router": mat(D, 4),
                            "router_bias": np.array([0.4, -0.3, 0.0, 0.2]),
                            "gate": mat(2, D, F), "up": mat(2, D, F),
                            "down": mat(2, F, D), "shared": swiglu(2 * F)},
                    "mixer_norm": scale(D), "mlp_norm": scale(D)}}


def norm(v, scale):
    return v / math.sqrt(np.mean(v * v) + 1e-6) * scale


def turn(v, pos, theta=100.0):
    """One head's rotary part at position ``pos``: pair ``i`` is
    elements (2i, 2i + 1), turned by ``pos * theta^(-2i/d)``."""
    d, out = len(v), np.array(v)
    for i in range(d // 2):
        a = pos * theta ** (-2.0 * i / d)
        out[2 * i] = v[2 * i] * math.cos(a) - v[2 * i + 1] * math.sin(a)
        out[2 * i + 1] = v[2 * i + 1] * math.cos(a) + v[2 * i] * math.sin(a)
    return out


def silu(v):
    return v / (1.0 + np.exp(-v))


def swiglu(p, u):
    return (silu(u @ p["gate"]) * (u @ p["up"])) @ p["down"]


def attention_by_hand(p, u):
    q = (u @ p["wq"]).reshape(T, H, DN + DR)
    a = u @ p["wkv_a"]
    kv = np.stack([norm(a[t, :R], p["kv_a_norm"]) @ p["wkv_b"]
                   for t in range(T)]).reshape(T, H, DN + DV)
    k_r = [turn(a[t, R:], t) for t in range(T)]
    out = np.zeros((T, H * DV))
    for t in range(T):
        for h in range(H):
            qt = np.concatenate([q[t, h, :DN], turn(q[t, h, DN:], t)])
            scores = np.array([
                qt @ np.concatenate([kv[s, h, :DN], k_r[s]])
                for s in range(t + 1)]) / math.sqrt(DN + DR)
            w = np.exp(scores - scores.max())
            w /= w.sum()
            out[t, h * DV:(h + 1) * DV] = sum(
                w[s] * kv[s, h, DN:] for s in range(t + 1))
    return out @ p["wo"]


def experts_by_hand(p, u):
    """([T, D], the layer's balance term, the loads)."""
    out, load = np.zeros((T, D)), np.zeros(4)
    for t in range(T):
        s = 1.0 / (1.0 + np.exp(-(u[t] @ p["router"])))
        chosen = np.argsort(-(s + p["router_bias"]), kind="stable")[:2]
        total = sum(s[e] for e in chosen) + 1e-20
        for e in chosen:
            load[e] += 1
            if e >= 2:      # the experts held: 2 and 3
                w = {k: p[k][e - 2] for k in ("gate", "up", "down")}
                out[t] += 2.448 * s[e] / total * swiglu(w, u[t])
        out[t] += swiglu(p["shared"], u[t])
    balance = -sum(p["router_bias"][e] * np.sign(load.mean() - load[e])
                   for e in range(4))
    return out, balance, load


def loss_by_hand(params, x):
    h = params["embed"][x]
    p = params["layer_0"]
    h = h + attention_by_hand(p["mixer"], np.stack(
        [norm(v, p["mixer_norm"]) for v in h]))
    h = h + swiglu(p["mlp"], np.stack([norm(v, p["mlp_norm"]) for v in h]))
    p = params["layer_1"]
    h = h + attention_by_hand(p["mixer"], np.stack(
        [norm(v, p["mixer_norm"]) for v in h]))
    o, balance, load = experts_by_hand(
        p["mlp"], np.stack([norm(v, p["mlp_norm"]) for v in h]))
    h = h + o
    logits = np.stack([norm(v, params["final_norm"]) for v in h]) \
        @ params["head"]
    ce = 0.0
    for t in range(T - 1):
        z = logits[t] - logits[t].max()
        ce -= (z - math.log(np.exp(z).sum()))[x[t + 1]]
    return ce / (T - 1), 0.05 * balance, load


def test_the_reference_is_the_equations_written_as_loops():
    params = seeded_params()
    x = np.array([3, 1, 7, 3])
    ce, balance, load = loss_by_hand(params, x)
    assert load.sum() == 2 * T and load.max() > load.mean()   # not even
    as32 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        loss, got_ce, got_balance = kanana2.objective(
            as32, jnp.asarray(x)[None], SPEC)
        grads = jax.grad(lambda p: kanana2.make_loss(SPEC)(
            p, jnp.asarray(x)[None], None))(as32)
    np.testing.assert_allclose(got_ce, ce, rtol=2e-5)
    np.testing.assert_allclose(got_balance, balance, rtol=1e-5)
    assert float(loss) == float(got_ce)         # L_B adds no value
    # the biases' gradient is minus u x the published direction, and
    # nothing of CE reaches them
    want = -0.05 * np.sign(load.mean() - load)
    np.testing.assert_allclose(grads["layer_1"]["mlp"]["router_bias"],
                               want, atol=1e-7)
    assert all(float(jnp.max(jnp.abs(g))) > 0
               for g in jax.tree.leaves(grads))


def test_the_casts_reach_every_bfloat16_product_and_not_the_router():
    """``cast`` (the controls' hook) moves the loss, and leaves the
    router's float32 product alone: with a cast that zeroes nothing but
    rounds, the tokens' choice is the uncast one."""
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                          seeded_params())
    x = jnp.asarray([[3, 1, 7, 3]])
    with jax.default_matmul_precision("highest"):
        plain = kanana2.make_loss(SPEC)(params, x, None)
        rounded = kanana2.make_loss(SPEC)(params, x, None,
                                         _ops.fp8_round_trip)
        u = jnp.asarray(np.random.RandomState(0).randn(T, D), jnp.float32)
        _, b0 = kanana2.experts(params["layer_1"]["mlp"], u, SPEC,
                                _ops.identity)
        _, b8 = kanana2.experts(params["layer_1"]["mlp"], u, SPEC,
                                _ops.fp8_round_trip)
    assert abs(float(plain) - float(rounded)) > 1e-4
    assert float(b0) == float(b8)
