"""``reference/keye_vl2.py``'s own arithmetic against a case written
out by hand in numpy float64: one layer, two query heads on one key
head, an indexer of two heads, two routed experts of which the chip
holds the second, one a token, two selected keys on four tokens; every
sum, rotation, selection and gate written as a loop."""
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import _ops, keye_vl2

SPEC = {"num_hidden_layers": 1, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 4, "rms_norm_eps": 1e-6,
        "rope_theta": 100.0, "num_experts_per_tok": 1,
        "norm_topk_prob": True, "routed_experts": 2,
        "first_expert_held": 1, "indexer_num_heads": 2,
        "indexer_head_dim": 2, "topk": 2, "q_chunk_size": 2}
D, F, V, T, H, HD, J, DI = 6, 5, 7, 4, 2, 4, 2, 2


def seeded_params():
    rng = np.random.RandomState(9)
    mat = lambda *shape: rng.randn(*shape) * 0.4
    scale = lambda n: 1.0 + 0.1 * rng.randn(n)
    return {
        "embed": mat(V, D), "head": mat(D, V), "final_norm": scale(D),
        "layer_0": {
            "mixer": {"wq": mat(D, H * HD), "wk": mat(D, HD),
                      "wv": mat(D, HD), "wo": mat(H * HD, D),
                      "q_norm": scale(HD), "k_norm": scale(HD),
                      "index_q": mat(D, J * DI), "index_k": mat(D, DI),
                      "index_w": mat(D, J)},
            "mlp": {"router": mat(D, 2), "gate": mat(1, D, F),
                    "up": mat(1, D, F), "down": mat(1, F, D)},
            "mixer_norm": scale(D), "mlp_norm": scale(D)}}


def norm(v, scale):
    return v / math.sqrt(np.mean(v * v) + 1e-6) * scale


def turn(v, pos, theta=100.0):
    """One head's vector at position ``pos``: pair (i, i + d/2) turned
    by ``pos * theta^(-2i/d)``."""
    d, out = len(v), np.array(v)
    for i in range(d // 2):
        a = pos * theta ** (-2.0 * i / d)
        out[i] = v[i] * math.cos(a) - v[i + d // 2] * math.sin(a)
        out[i + d // 2] = v[i + d // 2] * math.cos(a) + v[i] * math.sin(a)
    return out


def silu(v):
    return v / (1.0 + np.exp(-v))


def layer_by_hand(p, x):
    """``x``: [T, D] -> ([T, D], the layer's KL term, each query's
    selected positions)."""
    m = p["mixer"]
    u = np.stack([norm(x[t], p["mixer_norm"]) for t in range(T)])
    k = [turn(norm(u[t] @ m["wk"], m["k_norm"]), t) for t in range(T)]
    v = u @ m["wv"]
    ki = [turn(u[t] @ m["index_k"], t) for t in range(T)]
    mixed, kl, chosen = np.zeros((T, H * HD)), 0.0, []
    for t in range(T):
        qi = (u[t] @ m["index_q"]).reshape(J, DI)
        w = (u[t] @ m["index_w"]) * (J * DI) ** -0.5
        score = [sum(w[j] * max(turn(qi[j], t) @ ki[s], 0.0)
                     for j in range(J)) for s in range(t + 1)]
        best = sorted(sorted(range(t + 1), key=lambda s: -score[s])[:2])
        chosen.append(best)
        summed = np.zeros(len(best))
        for h in range(H):
            q = turn(norm((u[t] @ m["wq"])[h * HD:(h + 1) * HD],
                          m["q_norm"]), t)
            e = np.array([math.exp(q @ k[s] / math.sqrt(HD)) for s in best])
            e /= e.sum()
            summed += e
            mixed[t, h * HD:(h + 1) * HD] = sum(
                e[i] * v[s] for i, s in enumerate(best))
        target = summed / H
        law = np.array([math.exp(score[s]) for s in best])
        law /= law.sum()
        kl += sum(a * math.log(a / b) for a, b in zip(target, law))
    a = x + mixed @ m["wo"]
    out = np.array(a)
    for t in range(T):
        u2 = norm(a[t], p["mlp_norm"])
        logit = u2 @ p["mlp"]["router"]
        r = np.exp(logit - logit.max())
        r /= r.sum()
        # one expert a token, its probability normalised to 1; the chip
        # holds expert 1 alone
        if int(np.argmax(r)) == 1:
            out[t] += (silu(u2 @ p["mlp"]["gate"][0])
                       * (u2 @ p["mlp"]["up"][0])) @ p["mlp"]["down"][0]
    return out, kl / T, chosen


def by_hand(p, ids):
    h, kl, chosen = layer_by_hand(p["layer_0"], p["embed"][ids])
    ce = 0.0
    for t in range(T - 1):
        logits = norm(h[t], p["final_norm"]) @ p["head"]
        ce -= logits[ids[t + 1]] - math.log(np.sum(np.exp(logits)))
    return ce / (T - 1), kl, chosen


def test_one_layer_written_out_by_hand():
    params = seeded_params()
    ids = [3, 6, 0, 3]
    want_ce, want_kl, chosen = by_hand(params, ids)
    # the case selects: the last two queries drop a key each
    assert [len(c) for c in chosen] == [1, 2, 2, 2]
    assert chosen[2] != [0, 1, 2] and chosen[3] != [1, 2, 3]
    p32 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    x = jnp.asarray([ids])
    with jax.default_matmul_precision("highest"):
        loss, ce, kl = keye_vl2.objective(p32, x, SPEC)
        same = keye_vl2.make_loss(SPEC)(p32, x, None)
    np.testing.assert_allclose(ce, want_ce, rtol=2e-5)
    np.testing.assert_allclose(kl, want_kl, rtol=2e-4)
    np.testing.assert_allclose(loss, want_ce + want_kl, rtol=2e-5)
    assert float(same) == float(loss)
    # the token that chose the absent expert passes its layer's
    # attention output on unchanged: some token did, some did not
    a = jnp.asarray(params["embed"][ids][None], jnp.float32)
    with jax.default_matmul_precision("highest"):
        out, _ = keye_vl2.layer(p32["layer_0"], a, SPEC, _ops.identity)
    hand, _, _ = layer_by_hand(params["layer_0"], params["embed"][ids])
    np.testing.assert_allclose(out[0], hand, atol=2e-5)


def test_the_control_hook_reaches_every_product():
    """Rounding the operands of every product moves the loss; the
    pointwise parts are not behind the hook, so identity leaves it."""
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                          seeded_params())
    x = jnp.asarray([[3, 6, 0, 3]])
    loss = keye_vl2.make_loss(SPEC)
    plain = float(loss(params, x, None))
    assert float(loss(params, x, None, _ops.identity)) == plain
    rounded = float(loss(params, x, None, _ops.bf16_round_trip))
    assert 1e-6 < abs(rounded - plain) / plain < 0.05


def test_specification_is_read_from_the_configurations_file():
    s = keye_vl2.load_spec()
    assert (s["num_hidden_layers"], s["num_attention_heads"],
            s["num_key_value_heads"], s["head_dim"], s["rope_theta"]) == (
        4, 32, 4, 128, 10000000)
    assert (s["routed_experts"], s["first_expert_held"],
            s["num_experts_per_tok"], s["norm_topk_prob"]) == (
        128, 0, 8, True)
    assert (s["indexer_num_heads"], s["indexer_head_dim"], s["topk"],
            s["q_chunk_size"]) == (16, 64, 2048, 512)
