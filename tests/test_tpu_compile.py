"""What the TPU's compiler makes of the round program, compiled here for
a described v5e chip (no chip attached, nothing runs).

The CPU cannot show it: the TPU pipeline re-types and re-lays-out
operands (its bf16 propagation hoists the model's input cast through
every data movement up to the program argument; its layout assignment
wants a gather's operand row-major) and the device keeps a
``[C, n_max, 32, 32, 3]`` store with ``n_max`` minor-most. With the
whole store as a gather's operand that was one cast-and-copy of all
``C x n_max`` images every round (PERF.md section 6, PR 25).

Keep TPU compiles in THIS file only, behind the fixture: one process at
a time may load the TPU's library, and the workers of a parallel run
each import every test file.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from fedtorch_tpu.algorithms import make_algorithm
from fedtorch_tpu.config import (
    DataConfig, ExperimentConfig, FederatedConfig, MeshConfig, ModelConfig,
    OptimConfig, TrainConfig,
)
from fedtorch_tpu.data.batching import ClientData
from fedtorch_tpu.models import define_model
from fedtorch_tpu.parallel import FederatedTrainer


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or its lock is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_compiled_round_holds_no_operation_of_the_stores_size(one_chip):
    """bf16 compute, flip-and-crop, 'batch' gather: no instruction of
    the compiled program produces an array of the store's shape, in any
    type or layout — the store passes through as the argument it is."""
    C, n_max = 4, 256
    cfg = ExperimentConfig(
        data=DataConfig(dataset="cifar10", batch_size=6, augment=True),
        federated=FederatedConfig(
            federated=True, num_clients=C, online_client_rate=0.5,
            algorithm="fedavg", sync_type="local_step"),
        model=ModelConfig(arch="cnn", norm="bn"),
        optim=OptimConfig(lr=0.05),
        train=TrainConfig(local_step=2),
        mesh=MeshConfig(num_devices=1, compute_dtype="bfloat16"),
    ).finalize()
    # the trainer is built on a small store of the same rank; the
    # program is compiled against the abstract one below
    small = ClientData(x=np.zeros((C, 16, 32, 32, 3), np.float32),
                       y=np.zeros((C, 16), np.int32),
                       sizes=np.full((C,), 16, np.int32))
    trainer = FederatedTrainer(
        cfg, define_model(cfg, batch_size=cfg.data.batch_size),
        make_algorithm(cfg), small)
    assert trainer.gather_mode == "batch"

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    server, clients = jax.eval_shape(trainer.init_state, jax.random.key(0))
    data = on_chip(ClientData(
        x=jax.ShapeDtypeStruct((C, n_max, 32, 32, 3), jnp.float32),
        y=jax.ShapeDtypeStruct((C, n_max), jnp.int32),
        sizes=jax.ShapeDtypeStruct((C,), jnp.int32)))
    text = jax.jit(trainer.round_fn).lower(
        on_chip(server), on_chip(clients), data).compile().as_text()
    made = re.compile(
        rf"%[\w.\-]+ = \w+\[{C},{n_max},32,32,3\]\S* ([\w\-]+)\(")
    passes_through = {"parameter", "get-tuple-element"}
    store_sized = [line.strip()[:200] for line in text.splitlines()
                   for m in [made.search(line)]
                   if m and m.group(1) not in passes_through]
    assert "fed.gather" in text   # the stage is there to be judged
    assert not store_sized, "\n".join(store_sized)


def test_delta_rule_gradient_holds_no_triangular_solve(one_chip):
    """The chunk's unit lower-triangular inverse is the module's own
    batched substitution (``ops/delta_rule.py:unit_lower_inverse``) in
    the forward pass and its own two products in the backward pass: at
    the olmo cell's shapes the compiled gradient holds neither XLA's
    generic inverter of diagonal blocks (2.6 ms a call on the chip,
    PERF.md section 6, PR 36) nor a ``triangular-solve``."""
    from fedtorch_tpu.ops.delta_rule import chunk_gated_delta_rule

    B, T, H, dk, dv = 1, 2048, 30, 96, 192
    on_chip = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    args = (on_chip((B, T, H, dk), jnp.bfloat16),
            on_chip((B, T, H, dk), jnp.bfloat16),
            on_chip((B, T, H, dv), jnp.bfloat16),
            on_chip((B, T, H), jnp.float32),
            on_chip((B, T, H), jnp.float32))
    loss = lambda *a: jnp.sum(chunk_gated_delta_rule(*a))
    text = jax.jit(jax.grad(loss, argnums=range(5))).lower(
        *args).compile().as_text()
    assert "delta.inverse" in text     # the inverse is there to be judged
    assert "InvertDiagBlocksLowerTriangular" not in text
    assert not re.search(r"\btriangular-solve\(", text)


def _computations(text):
    """``{name: lines}`` of a compiled module's computations, the names
    of those a ``fusion`` calls, and of those inside a ``while`` body
    (the body and what it calls, to any depth)."""
    lines, name = {}, None
    for line in text.splitlines():
        m = re.match(r"(?:ENTRY )?%([\w.\-]+) \(", line)
        if m:
            name = m.group(1)
            lines[name] = []
        elif name is not None:
            lines[name].append(line)
    called = {n: set(re.findall(
        r"(?:calls|body|condition|to_apply)=%([\w.\-]+)", "\n".join(ls)))
        for n, ls in lines.items()}
    fused = {c for ls in lines.values() for line in ls if " fusion(" in line
             for c in re.findall(r"calls=%([\w.\-]+)", line)}
    looped = {c for ls in lines.values() for line in ls
              for c in re.findall(r"body=%([\w.\-]+)", line)}
    grown = True
    while grown:
        more = set().union(*(called[n] for n in looped)) - looped
        grown = bool(more)
        looped |= more
    return lines, fused, looped


@pytest.mark.parametrize("per_token,block", [(8, 8192), (6, 6144)])
def test_expert_share_compiles_to_grouped_kernels_at_the_cells_widths(
        one_chip, per_token, block):
    """The Keye cell's expert layer (8 experts a token) and the kanana
    cell's (6) at their real shapes (4096 tokens of 2048, 16 experts of
    768 held of 128: a buffer of 32 768 / 24 576 rows, a row block of
    8192 / 6144), forward and backward under the layer's checkpoint:
    every grouped product is the TPU's own grouped-matmul kernel
    (``ragged-dot`` custom calls that visit the row tiles in use), none
    was expanded into a dense product over all the experts (16 times
    the work). Outside the loop over the further blocks NO array of the
    buffer's rows is written but the two sums that bring a block's rows
    back to their tokens (XLA:TPU does not fuse a gather into the sum
    that reads it: ``[per_token, tokens, 2048]``, float32 for the
    result and the compute type for the tokens' cotangent): no fill,
    mask, activation, cast or product result of ``tokens x per_token``
    rows; the temporaries are a sixth of what the whole buffer took
    (under 3 GiB then)."""
    from fedtorch_tpu.ops import routed_experts

    T, d, f, held, routed = 4096, 2048, 768, 16, 128
    assert routed_experts.block_rows(T, per_token, held, routed) == block
    on_chip = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    p = {"gate": on_chip((held, d, f)), "up": on_chip((held, d, f)),
         "down": on_chip((held, f, d))}

    @functools.partial(
        jax.checkpoint, policy=jax.checkpoint_policies.save_only_these_names(
            "mlp.gate", "mlp.up"))
    def layer(p, u, router):
        gates, chosen = routed_experts.route(u @ router, per_token, True)
        return routed_experts.expert_share(
            p, u, gates, chosen, first=0, dt=jnp.bfloat16, block=block,
            scopes=("lm.router", "lm.experts"))[0]

    def loss(p, u, router):
        out = layer(p, u, router)
        return jnp.sum(out * out)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        p, on_chip((T, d)), on_chip((d, routed))).compile()
    text = compiled.as_text()
    kernels = re.findall(r'op_name="[^"]*(ragged-dot[\w-]*)"', text)
    assert len([k for k in kernels if "metadata" not in k]) >= 9, kernels
    # no [experts, rows, width] array: the dense expansion's operand
    rows = T * per_token
    assert not re.search(rf"\[16,(?:{rows}|{block}),", text)
    lines, fused, looped = _computations(text)
    assert looped, "the further blocks' loop is there to be judged"
    made = re.compile(
        rf"%[\w.\-]+ = (\w+)\[(?:{rows}|{per_token},{T}),(?:{d}|{f})\]"
        r"\S* ([\w\-]+)\(")
    passes_through = {"parameter", "get-tuple-element", "bitcast"}
    written = [(m.group(1), line.strip())
               for name, ls in lines.items()
               if name not in fused and name not in looped
               for line in ls for m in [made.search(line)]
               if m and m.group(2) not in passes_through]
    assert sorted(t for t, _ in written) == ["bf16", "f32"], \
        [line[:200] for _, line in written]
    assert all("/gather" in line for _, line in written)
    # every operation of the loop's body and of the backward rule
    # carries a scope the device trace's reader sums
    ops = re.findall(r'op_name="([^"]*)"', text)
    body = [o for o in ops if "/while/body/" in o]
    assert body and all("lm.experts" in o or "lm.router" in o for o in body)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 640 * 2 ** 20, temp


def test_selected_attention_compiles_in_chunks_at_the_cells_length(
        one_chip, monkeypatch):
    """The Keye cell's attention over selected keys at 4096 tokens (32
    query on 4 key heads of 128, an indexer of 16 heads of 64, 2048 keys
    a query, chunks of 512), forward and backward, as a TPU builds it:
    the three fused kernels (``ops/pallas/selected_attention.py``) are
    in the compiled program; no array of a whole row's scores lives
    (``[.., 4096, 4096]``), none of a chunk's heads' float32 scores
    (``[1, 4, 8, 512, keys]`` or ``[32, 512, keys]``), and the temporaries
    stay under 1 GiB where a row's scores alone are 2.1 GB."""
    from fedtorch_tpu.ops import attention_dispatch, sparse_attention

    # the program asks the backend, which is the CPU here: answer for
    # the chip the program is compiled for
    monkeypatch.setattr(attention_dispatch, "on_tpu", lambda: True)
    T = 4096
    assert sparse_attention.takes_kernel(32, 4, 128, 512, T)
    on_chip = lambda *shape: jax.ShapeDtypeStruct(
        shape, jnp.float32, sharding=one_chip)
    args = (on_chip(1, T, 32, 128), on_chip(1, T, 4, 128),
            on_chip(1, T, 4, 128), on_chip(1, T, 16, 64),
            on_chip(1, T, 64), on_chip(1, T, 16))

    def loss(*a):
        out, index_loss = sparse_attention.selected_attention(
            *a, topk=2048, chunk=512, dt=jnp.bfloat16)
        return jnp.sum(out * out) + index_loss

    compiled = jax.jit(jax.grad(loss, argnums=range(6))).lower(
        *args).compile()
    text = compiled.as_text()
    kernels = set(re.findall(r"selected_attention_(?:fwd|target|bwd)",
                             text))
    assert len(kernels) == 3 and "tpu_custom_call" in text, kernels
    assert not re.search(r"4096,4096\]", text)
    # (the rows' statistics are [1,4,8,512,8]: eight lanes, not keys)
    assert not re.search(r"f32\[(?:1,4,8,512|32,512),\d{4}", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


def test_flash_attention_compiles_at_the_latent_cells_heads(one_chip,
                                                            monkeypatch):
    """The kanana cell's softmax attention at 4096 tokens (32 heads,
    192 wide for queries and keys and 128 for values), forward and
    backward, as a TPU builds it: Mosaic takes blocks whose last
    dimension is the head's whole 192 (one and a half lane tiles) with a
    value tile and an accumulator of 128 beside them, no operand is
    padded to the other's size; both directions are kernels, so no
    array of a query block's scores lives (the chunked scan's were
    ``f32[32,512,4096]``, 256 MiB each) and no float32 copy of ``q`` or
    ``k``."""
    import fedtorch_tpu.ops.pallas.flash_attention as fa

    monkeypatch.setattr(fa, "on_tpu", lambda: True)
    T = 4096
    q = jax.ShapeDtypeStruct((1, T, 32, 192), jnp.bfloat16,
                             sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, T, 32, 128), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, q, v).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "flash_attention_fwd" in text and "flash_attention_bwd" in text
    assert not re.search(r"4096,4096\]", text)
    assert not re.search(r"\[32,4096,192\][^\n]* pad\(", text)
    assert "f32[32,512,4096]" not in text
    assert "f32[32,4096,192]" not in text
    assert "while(" not in text
    # read 353 MiB (the layout copies between [B, T, H, D] and
    # [BH, T, D] and the float32 cotangent of this test's loss); the
    # scan's program read 682 MiB
    assert compiled.memory_analysis().temp_size_in_bytes < 512 * 2 ** 20


def test_flash_kernels_take_bfloat16_operands_under_highest_precision(
        one_chip, monkeypatch):
    """A caller's ``default_matmul_precision('highest')`` (the tests',
    chip_smoke's) reaches a kernel's products when it is traced; Mosaic
    refuses it on bfloat16 operands ("Bad lhs type"), whose product is
    exact in one pass anyway: the kernels ask for it on float32
    operands alone."""
    import fedtorch_tpu.ops.pallas.flash_attention as fa

    monkeypatch.setattr(fa, "on_tpu", lambda: True)
    x = jax.ShapeDtypeStruct((1, 1024, 2, 128), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32) ** 2)

    with jax.default_matmul_precision("highest"):
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            x, x, x).compile().as_text()
    assert "flash_attention_fwd" in text and "flash_attention_bwd" in text

