"""Deviceless compiles for the chip: what the TPU's compiler must accept.

Every Pallas kernel of the main path is compiled here — not run — for a
described, unattached ``v5e:2x2`` topology, at GPT-2 124M widths
(H=12, D=64, L=1024, vocab 50257) and the Llama-300M GQA fold (G=8, R=2).
Interpret mode cannot show what this does: Mosaic's block-shape, alignment
and VMEM rules. A pass here is NOT a chip run (nothing executes; results and
times come from ``chip_smoke.py``).

ONE file on purpose: only one process at a time may load the TPU's library,
and it keeps it until it exits — so the topology is described inside a
module-scoped fixture (never at import, in a ``skipif`` or in ``conftest``),
every compile happens in this process, and no other test file describes a
topology in-process. The persistent compilation cache is off around these
compiles: a deviceless entry can be written but not read back.
"""

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from distributeddeeplearning_tpu import data as data_lib
from distributeddeeplearning_tpu import models
from distributeddeeplearning_tpu.mesh import MeshConfig, build_mesh
from distributeddeeplearning_tpu.ops import (
    flash_attention,
    fused_adamw,
    paged_attention,
    ring_attention_pallas,
)
from distributeddeeplearning_tpu.ops.chunked_xent import (
    chunked_xent,
    head_output,
)
from distributeddeeplearning_tpu.sharding import make_rules
from distributeddeeplearning_tpu.train import (
    Trainer, get_task, make_optimizer,
)
from distributeddeeplearning_tpu.utils.hlo import (
    collective_bytes,
    collective_counts,
)

_TOPOLOGY = "v5e:2x2"
# GPT-2 124M
H, D, L, V, E = 12, 64, 1024, 50257, 768


@pytest.fixture(scope="module")
def topo():
    from jax._src import compilation_cache as cc
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name=_TOPOLOGY
        )
    except Exception as e:  # whatever libtpu raises here is the reason
        pytest.skip(
            f"no {_TOPOLOGY} topology can be described here: "
            f"{type(e).__name__}: {e}"
        )
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *abstract_args) -> str:
    return jax.jit(fn).lower(*abstract_args).compile().as_text()


def _assert_kernel(text: str) -> None:
    assert "tpu_custom_call" in text, "no Mosaic kernel in the compiled text"


def _qkv(sharding, seq=L):
    return [jax.ShapeDtypeStruct((16, seq, H, D), jnp.bfloat16,
                                 sharding=sharding)] * 3


@pytest.mark.parametrize("seq", [L, 1000], ids=["L1024", "L1000-padded"])
def test_flash_forward_compiles(one_chip, seq):
    _assert_kernel(_compiled_text(
        lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=False
        ),
        *_qkv(one_chip, seq),
    ))


def test_flash_backward_compiles(one_chip):
    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=False)
        return jnp.mean(out.astype(jnp.float32) ** 2)

    _assert_kernel(_compiled_text(
        jax.grad(loss, argnums=(0, 1, 2)), *_qkv(one_chip)
    ))


def test_ring_attention_pallas_cp1_compiles(topo):
    mesh = build_mesh(MeshConfig(dp=1), devices=[topo.devices[0]])
    _assert_kernel(_compiled_text(
        lambda q, k, v: ring_attention_pallas(
            q, k, v, mesh, causal=True, interpret=False
        ),
        *_qkv(NamedSharding(mesh, P())),
    ))


def test_fused_adamw_compiles(one_chip):
    shapes = {"wte": (V, E), "qkv": (E, 3 * E), "bias": (E,), "odd": (7,)}
    params = {
        k: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
        for k, s in shapes.items()
    }
    tx = fused_adamw(1e-3, weight_decay=0.1, grad_clip=1.0, interpret=False)

    def step(p, g):
        return tx.update(g, tx.init(p), p)

    _assert_kernel(_compiled_text(step, params, params))


# (kv_heads, num_rep): GPT-2 124M is MHA, Llama-300M folds 16 heads on 8.
@pytest.mark.parametrize("layout", [(12, 1), (8, 2)], ids=["gpt2", "llama"])
@pytest.mark.parametrize("quantized", [False, True], ids=["fp", "int8"])
def test_paged_attention_compiles(one_chip, layout, quantized):
    G, R = layout
    B, NB, BS, pages = 8, 512, 16, 64

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = S((NB, BS, G, D), jnp.int8 if quantized else jnp.bfloat16)
    args = [S((B, G * R, D), jnp.bfloat16), pool, pool,
            S((B, pages), jnp.int32), S((B,), jnp.int32)]
    if quantized:
        args += [S((NB, BS, G), jnp.float32)] * 2

    def fn(q, pk, pv, table, lens, sk=None, sv=None):
        return paged_attention(
            q, pk, pv, table, lens, scale_k=sk, scale_v=sv, num_rep=R,
            interpret=False,
        )

    _assert_kernel(_compiled_text(fn, *args))


def test_chunked_xent_loss_and_grad_compile(one_chip):
    hidden = jax.ShapeDtypeStruct((16, L, E), jnp.bfloat16, sharding=one_chip)
    emb = jax.ShapeDtypeStruct((V, E), jnp.bfloat16, sharding=one_chip)
    targets = jax.ShapeDtypeStruct((16, L), jnp.int32, sharding=one_chip)

    def loss(h, e, t):
        return jnp.mean(chunked_xent(head_output(h, e), t, seq_chunk=128))

    text = _compiled_text(
        jax.value_and_grad(loss, argnums=(0, 1)), hidden, emb, targets
    )
    # Pure XLA (no kernel); what must hold is that the [16, 1024, 50257]
    # fp32 logits (3.3 GB) never exist as one buffer.
    assert "f32[16,1024,50257]" not in text


def _aot_step_text(trainer, ds) -> str:
    """AOT-compile the REAL train step for described devices; nothing is
    materialized."""
    return trainer.lower_train_step(ds.batch(0)).compile().as_text()


def _moe_step_text(mesh, rules=None) -> str:
    model = models.get_model(
        "gpt2_moe", size="tiny", vocab_size=64, max_len=32,
        dropout_rate=0.0, num_experts=4, moe_every=2,
    )
    ds = data_lib.SyntheticTokens(
        batch_size=16, seq_len=16, vocab_size=64, seed=0
    )
    kw = dict(donate=False)
    if rules is not None:
        kw["rules"] = rules
    trainer = Trainer(
        model, make_optimizer("adamw", 1e-3), get_task("lm"), mesh, **kw
    )
    return _aot_step_text(trainer, ds)


def test_ep_token_exchange_lowers_to_all_to_all_on_four_chips(topo):
    # The EP token exchange lowers in gather form on the CPU SPMD pipeline;
    # only the TPU pipeline emits the GShard dispatch/combine as true
    # all-to-alls. With the expert rule deleted the experts replicate and no
    # token exchange exists at all — so the assert fails iff the EP
    # constraints are deleted, not because "some collective" showed up.
    devices = list(topo.devices)
    assert len(devices) == 4
    mesh = build_mesh(MeshConfig(dp=1, ep=4), devices=devices)
    ep = collective_counts(_moe_step_text(mesh))
    control = collective_counts(
        _moe_step_text(mesh, rules=make_rules(expert=None))
    )
    assert ep["all-to-all"] > 0, ep
    assert control["all-to-all"] == 0, control


def test_int8_grad_comm_step_lowers_on_four_chips(topo):
    # The manual data-parallel step (shard_map over dp, compressed ring):
    # ring permutes on int8 payloads survive the TPU lowering.
    mesh = build_mesh(MeshConfig(dp=4), devices=list(topo.devices))
    model = models.get_model(
        "gpt2", size="tiny", vocab_size=64, max_len=32, dropout_rate=0.0
    )
    ds = data_lib.SyntheticTokens(
        batch_size=16, seq_len=32, vocab_size=64, seed=0
    )
    trainer = Trainer(
        model, make_optimizer("adamw", 1e-3), get_task("lm"), mesh,
        donate=False, grad_comm="int8",
    )
    text = _aot_step_text(trainer, ds)
    assert collective_bytes(text, 4)["collective-permute"], (
        "TPU lowering of the quantized step has no ring permutes"
    )
    assert "s8[" in text
