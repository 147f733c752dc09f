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

import re
import sys

import numpy as np
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


@pytest.mark.parametrize("shape,causal", [
    # The training cell's own call (benchmarks/configs/gpt2_medium.json,
    # traffic fit_b8_s1024) at the derived default tiles: VMEM fits, and the
    # names the benchmark's readers look for are kept.
    ((8, 1024, 16, 64), True),
    # 16K rows: K and V no longer stay in VMEM whole (several major blocks).
    ((1, 16384, 8, 128), True),
    # ViT's 197 tokens, not causal: padded to 256, one tile.
    ((4, 197, 12, 64), False),
], ids=["train_cell", "L16384-major-blocks", "L197-padded-noncausal"])
def test_flash_default_tiles_compile(one_chip, shape, causal):
    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, interpret=False)

    qkv = [jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)] * 3
    assert _kernel_names(_compiled_text(flash, *qkv)) == {"flash_fwd"}
    got = _kernel_names(_compiled_text(
        jax.grad(_sq_mean(flash), argnums=(0, 1, 2)), *qkv
    ))
    assert _untransformed(got) == {
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"
    }


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

    pool = S((NB, BS, G * D), jnp.int8 if quantized else jnp.bfloat16)
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


# (kv_heads, num_rep, head_dim, block_size, pool dtype, q dtype): what
# engine.read_path calls built beside the served cells' shapes, and so what
# a default user on a TPU gets: any dtype, any block of whole sublane
# tiles, any width of whole 128-lane rows, wide GQA.
@pytest.mark.parametrize("case", [
    (12, 1, 64, 16, jnp.float32, jnp.float32),
    (12, 1, 64, 8, jnp.bfloat16, jnp.bfloat16),
    (12, 1, 64, 128, jnp.bfloat16, jnp.bfloat16),
    (8, 16, 128, 16, jnp.bfloat16, jnp.bfloat16),
    (12, 1, 64, 16, jnp.bfloat16, jnp.float32),
    (2, 1, 64, 16, jnp.bfloat16, jnp.bfloat16),
], ids=["f32", "block8", "block128", "gqa16", "f32-q-bf16-pool", "width128"])
def test_paged_attention_compiles_wherever_the_rule_takes_it(one_chip, case):
    G, R, head_dim, BS, pool_dtype, q_dtype = case
    B, NB, pages = 8, 256, 1024 // BS

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = S((NB, BS, G * head_dim), pool_dtype)
    _assert_kernel(_compiled_text(
        lambda q, pk, pv, table, lens: paged_attention(
            q, pk, pv, table, lens, num_rep=R, interpret=False),
        S((B, G * R, head_dim), q_dtype), pool, pool,
        S((B, pages), jnp.int32), S((B,), jnp.int32),
    ))


def test_paged_attention_refuses_a_width_the_chip_cannot_copy():
    # kv_heads * head_dim under 128 lanes is a padded pool: no copy on the
    # chip slices a page out of it (Mosaic: "must be aligned to tiling").
    # The rule sends such a model to the gather; a demand fails by name.
    pool = jnp.zeros((8, 16, 64), jnp.bfloat16)
    with pytest.raises(NotImplementedError, match="pool width 64"):
        paged_attention(
            jnp.zeros((2, 1, 64), jnp.bfloat16), pool, pool,
            jnp.zeros((2, 4), jnp.int32), jnp.zeros((2,), jnp.int32),
            interpret=False,
        )


def _gpt2_engine_case():
    model = models.get_model(
        "gpt2", size="124m", num_layers=2, vocab_size=512, max_len=256,
        dtype=jnp.bfloat16,
    )
    params = model.init(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)
    )["params"]
    # K and V of two layers, 12 heads x 64 folded into the minor dimension
    return model, params, 24, r"pool_(?:key|value)", 4, H * D


def _glm_engine_case():
    # The served cell's widths (benchmarks/configs/glm47_flash.json): the
    # latent leaf holds 512 + 64 values a token, stored 640 wide. One
    # dense and one expert layer; the parameters stay shapes (1.4 GB real).
    import flax

    model = models.get_model(
        "glm4_moe_lite", num_layers=2, vocab_size=512, max_len=256,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
    )
    params = flax.core.meta.unbox(jax.eval_shape(
        model.init, jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)
    )["params"])
    return model, params, 10, r"pool_latent", 2, 640


@pytest.fixture(scope="module")
def compile_for_chip(one_chip):
    """What stands in for ``ServingEngine._compile``: the same function,
    operands and donation, compiled for the described chip."""
    def compile_for_chip(fn, *args, name=None, donate_argnums=()):
        abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), args,
        )
        return jax.jit(fn, donate_argnums=donate_argnums).lower(
            *abstract
        ).compile()

    return compile_for_chip


@pytest.fixture(scope="module")
def engine_program(compile_for_chip):
    """``(case, program) -> (engine, compiled text)``: the engine's OWN
    programs, with the engine's own operands and donation, compiled for
    the chip once a module however many tests read the text."""
    from distributeddeeplearning_tpu.config import ServingConfig
    from distributeddeeplearning_tpu.serving import ServingEngine

    built = {}

    def build(case, program):
        if (case, program) not in built:
            model, params, budget_mb = case()[:3]
            # The chip's engine, as the benchmark's files build it: it
            # demands nothing (attn_kernel="reference") and is told the
            # platform it is compiled for, so its rule reads what it
            # would on the chip.
            eng = ServingEngine(model, params, ServingConfig(
                slots=8, block_size=16, hbm_budget_mb=budget_mb,
                max_seq_len=256, prompt_buckets=(32,),
                attn_kernel="reference",
            ), platform="tpu")
            assert eng.num_blocks == 256
            eng._compile = compile_for_chip
            # The kernel asks jax.default_backend(), the CPU here: steered
            # to Mosaic from outside for the length of this compile.
            kernel_mod = sys.modules[paged_attention.__module__]
            interpret = kernel_mod._default_interpret
            kernel_mod._default_interpret = lambda: False
            try:
                exe = (eng._decode_exe_or_compile() if program == "decode"
                       else eng._prefill_exe_for(32))
            finally:
                kernel_mod._default_interpret = interpret
            text = exe.as_text()
            assert text.startswith(f"HloModule jit__{program}_fn")
            built[case, program] = eng, text
        return built[case, program]

    return build


def _each_engine_program(test):
    test = pytest.mark.parametrize(
        "case", [_gpt2_engine_case, _glm_engine_case],
        ids=["gpt2", "glm4_moe_lite"],
    )(test)
    return pytest.mark.parametrize("program", ["decode", "prefill"])(test)


@_each_engine_program
def test_engine_programs_take_the_pool_as_it_lies(
        engine_program, case, program):
    # The counter of the lane-dense pool (PERF.md §6, PR 25): a leaf whose
    # minor dims pad badly is stored block-index-minor by the runtime, and
    # every serving program then re-lays the whole pool out, three copies
    # a leaf a call. It engages always or never and a CPU run cannot see
    # it, so: the engine's programs at real head geometry (12 x 64; the
    # latent leaf of 576 values, which is stored block-index-minor unless
    # padded to 640: PR 26), compiled for the chip.
    _, _, _, leaf_name, n_leaves, width = case()
    eng, text = engine_program(case, program)

    leaves = eng._pool_leaves()
    assert len(leaves) == n_leaves
    assert {(leaf.shape, str(leaf.dtype)) for leaf in leaves} == {
        ((256, 16, width), "bfloat16")
    }
    shape = rf"bf16\[256,16,{width}\]"
    pool_params = dict(re.findall(
        rf"%(\S*{leaf_name}\S*) = {shape}\S* parameter\((\d+)\)", text
    ))
    assert len(pool_params) == n_leaves, pool_params
    # The declared order is the stored order: nothing to undo.
    entry = re.findall(rf"{shape}\{{([\d,]+)", text.split("\n")[0])
    assert entry and set(entry) == {"2,1,0"}, entry
    copies = re.findall(rf"= {shape}\S* copy\(", text)
    # What a lane's whole table gathers to: [slots, pages, block, width].
    gathered = re.findall(rf"bf16\[8,16,16,{width}\]", text)
    in_place = leaf_name != "pool_latent"
    assert eng.stats()["read_path"] == ("in_place" if in_place else "gather")
    if program == "decode":
        assert not copies, copies
        # The K/V pools' live pages are read where they lie, by the
        # engine's own rule: the kernel's call and no gather of the pool;
        # the latent leaf keeps the gather (no in-place latent read yet).
        assert ("paged_decode" in _kernel_names(text)) == in_place
        assert bool(gathered) == (not in_place), gathered
        aliased = set(re.findall(
            r"\((\d+), \{\}, (?:may|must)-alias\)",
            re.search(r"input_output_alias=\{(.*?\)) \}", text).group(1),
        ))
        assert set(pool_params.values()) <= aliased, (pool_params, aliased)
    else:
        # Prefill is not donated (engine._prefill_exe_for says why): the
        # one plain copy of a leaf into its output may remain, no more.
        # It is L > 1 and keeps the gather whatever the decode path.
        assert len(copies) <= len(leaves), copies
        assert "paged_decode" not in _kernel_names(text)
    if leaf_name == "pool_latent":
        # The grouped expert product is the compiler's own kernel: no
        # dense dispatch, and no Pallas kernel of this repo to name.
        assert "ragged-dot" in text
        ops = set(re.findall(r'op_name="([^"]*)"', text))
        for scope in ("mla_project", "mla_attend", "latent_write",
                      "moe_route", "moe_experts", "moe_shared"):
            assert any(f"/{scope}/" in o for o in ops), scope


def test_window_and_global_layers_decode_on_both_pools_as_they_lie(
        compile_for_chip):
    # The Command A+ family at the served cell's widths
    # (benchmarks/configs/command_a_plus.json: 128 query heads on 8 KV
    # heads of 128, so K and V are 1,024 wide; a window layer and a global
    # one; 2 of 128 experts held; the parameters stay shapes). Its decode
    # program takes both kinds of pool as they lie: every leaf of either
    # kind a donated parameter in its declared order, no pool-shaped copy;
    # a window layer gathers its ring (5 blocks of 16 at window 64) and
    # the global layer every page; the held experts run as the compiler's
    # grouped product under the scopes the trace is read by.
    import flax

    from distributeddeeplearning_tpu.config import ServingConfig
    from distributeddeeplearning_tpu.serving import ServingEngine

    model = models.get_model(
        "cohere2_moe", layer_types=("sliding_attention", "full_attention"),
        held_experts=(0, 2), vocab_size=512, max_len=256, window=64,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
    )
    params = flax.core.meta.unbox(jax.eval_shape(
        model.init, jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)
    )["params"])
    eng = ServingEngine(model, params, ServingConfig(
        slots=8, block_size=16, hbm_budget_mb=19, max_seq_len=256,
        prompt_buckets=(32,),
    ), platform="tpu")
    assert (eng.num_blocks, eng.window_blocks, eng.window_ring) == (263, 41, 5)
    eng._compile = compile_for_chip
    text = eng._decode_exe_or_compile().as_text()
    assert text.startswith("HloModule jit__decode_fn")
    # Window layers: the rule takes the gather for the whole model.
    assert eng.stats()["read_path"] == "gather"
    assert "paged_decode" not in _kernel_names(text)
    pool_params = {}
    for name, rows in (("pool_(?:key|value)_window", 41),
                       ("pool_(?:key|value)", 263)):
        shape = rf"bf16\[{rows},16,1024\]"
        found = dict(re.findall(
            rf"%(\S*{name}\S*) = {shape}\S* parameter\((\d+)\)", text
        ))
        assert len(found) == 2, (name, found)
        pool_params.update(found)
        entry = re.findall(rf"{shape}\{{([\d,]+)", text.split("\n")[0])
        assert entry and set(entry) == {"2,1,0"}, entry
        assert not re.findall(rf"= {shape}\S* copy\(", text)
    aliased = set(re.findall(
        r"\((\d+), \{\}, (?:may|must)-alias\)",
        re.search(r"input_output_alias=\{(.*?\)) \}", text).group(1),
    ))
    assert set(pool_params.values()) <= aliased, (pool_params, aliased)
    # what each kind gathers of a lane: the ring, and every page
    assert re.search(r"bf16\[8,5,16,1024\]", text)
    assert re.search(r"bf16\[8,16,16,1024\]", text)
    assert "ragged-dot" in text
    ops = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("attn_window", "attn_global", "kv_write", "moe_route",
                  "moe_experts", "moe_shared"):
        assert any(f"/{scope}/" in o for o in ops), scope


def _hlo_computations(text: str) -> dict:
    """name -> body of every computation of a compiled module's text."""
    return {m.group(1): m.group(2) for m in re.finditer(
        r"^(?:ENTRY )?%(\S+) \(.*?\{\n(.*?)^\}$", text, re.M | re.S
    )}


def _hlo_reachable(comps: dict, roots) -> set:
    """The computations ``roots`` call, directly or through others."""
    seen, todo = set(), list(roots)
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for one, group in re.findall(
            r"(?:to_apply|calls|body|condition|true_computation"
            r"|false_computation)=%([\w.\-]+)"
            r"|branch_computations=\{([^}]*)\}", comps[name],
        ):
            todo += [one] if one else re.findall(r"%([\w.\-]+)", group)
    return seen


@_each_engine_program
def test_engine_programs_sort_the_vocabulary_only_inside_a_branch(
        engine_program, case, program):
    # PR 29: a served program ends in one conditional over three samplers
    # (engine.sampler_arm) and the whole-vocabulary sort belongs to the
    # last. A CPU run cannot see whether the chip skips an arm; this pins
    # that the chip's compiler kept the branch (it may flatten a cheap
    # conditional into a select, which would run every arm) and hoisted no
    # sort out of it: every lane's logits are [lanes, 512] here, and no
    # sort of that width lies in the entry computation or anywhere the
    # greedy and plain arms reach. The router's own small sorts (64
    # scores, 32 or 128 pairs: glm4_moe_lite) stay where they were.
    _, text = engine_program(case, program)
    comps = _hlo_computations(text)
    sampler = [
        re.findall(r"%([\w.\-]+)", m.group(1))
        for body in comps.values()
        for m in re.finditer(
            r" conditional\(.*branch_computations=\{([^}]*)\}"
            r".*op_name=\"[^\"]*/sample/cond\"", body)
    ]
    assert len(sampler) == 1, sampler
    greedy, plain, filtered = sampler[0]
    wide = {
        name for name, body in comps.items()
        if re.search(r"= \(?\w+\[\d+,512\]\S*.* sort\(", body)
    }
    assert wide and wide <= _hlo_reachable(comps, [filtered]), wide
    assert not wide & _hlo_reachable(comps, [greedy, plain])
    # The greedy arm hands back the argmax and the rng it was given.
    assert set(re.findall(r" ([\w\-]+)\(", comps[greedy])) <= {
        "parameter", "get-tuple-element", "tuple", "bitcast", "copy",
    }, comps[greedy]


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


# ---------------------------------------------------------------------------
# names on the device (docs/OBSERVABILITY.md): what a profile, and the
# benchmark's per-kernel readers, find the kernels and programs by
# ---------------------------------------------------------------------------


def _kernel_names(text: str) -> set:
    """The instruction names of the Mosaic custom calls in compiled HLO
    text, less their number: ``%flash_fwd.1 = ... custom-call(...)
    custom_call_target="tpu_custom_call"`` -> ``flash_fwd``."""
    names = re.findall(
        r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text
    )
    return {re.sub(r"\.\d+$", "", n) for n in names}


def _untransformed(names: set) -> set:
    """jax wraps the scope next inside a transform in the transform's name
    (``transpose(jvp(flash_bwd_dq))``, spelt ``transpose_jvp_flash_bwd_dq__``
    in an instruction's name). In a model the scope next inside is the
    model's outermost, far from the kernel; differentiated bare, as here,
    it is the kernel's own."""
    return {re.sub(r"^(?:transpose_|jvp_)+(.*?)_+$", r"\1", n) for n in names}


def _sq_mean(fn):
    return lambda *a: jnp.mean(fn(*a).astype(jnp.float32) ** 2)


def _flash(q, k, v):
    return flash_attention(q, k, v, causal=True, interpret=False)


def _kernel_case(case: str, topo, one_chip):
    """(function, abstract arguments) whose compiled text holds the case's
    kernels."""
    if case == "flash_forward":
        return _flash, _qkv(one_chip)
    if case == "flash_backward":
        return jax.grad(_sq_mean(_flash), argnums=(0, 1, 2)), _qkv(one_chip)
    if case.startswith("ring"):
        mesh = build_mesh(MeshConfig(dp=1), devices=[topo.devices[0]])

        def ring(q, k, v):
            return ring_attention_pallas(
                q, k, v, mesh, causal=True, interpret=False
            )

        fn = ring if case == "ring_forward" else jax.grad(
            _sq_mean(ring), argnums=(0, 1, 2)
        )
        return fn, _qkv(NamedSharding(mesh, P()))
    if case == "fused_adamw":
        params = {
            "w": jax.ShapeDtypeStruct((E, 3 * E), jnp.float32,
                                      sharding=one_chip)
        }
        tx = fused_adamw(1e-3, weight_decay=0.1, interpret=False)
        return (lambda p, g: tx.update(g, tx.init(p), p)), [params, params]
    assert case == "paged_decode", case
    B, NB, BS, pages = 8, 512, 16, 64
    S = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip
    )
    pool = S((NB, BS, H * D), jnp.bfloat16)
    return (
        lambda q, pk, pv, table, lens: paged_attention(
            q, pk, pv, table, lens, interpret=False
        ),
        [S((B, H, D), jnp.bfloat16), pool, pool, S((B, pages), jnp.int32),
         S((B,), jnp.int32)],
    )


@pytest.mark.parametrize("case,names", [
    ("flash_forward", {"flash_fwd"}),
    ("flash_backward", {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}),
    ("ring_forward", {"ring_fwd"}),
    ("ring_backward", {"ring_fwd", "ring_bwd_dq", "ring_bwd_dkv"}),
    ("fused_adamw", {"fused_adamw"}),
    ("paged_decode", {"paged_decode"}),
])
def test_kernel_instruction_carries_its_name(topo, one_chip, case, names):
    # The name a kernel has in a device trace is its instruction's: with no
    # ``name=`` it is the caller's scope (``attn``, ``step_fn``), which a
    # refactor of the caller changes under every reader's feet.
    fn, args = _kernel_case(case, topo, one_chip)
    got = _kernel_names(_compiled_text(fn, *args))
    assert _untransformed(got) == names
    assert all("flash" in n for n in got) or "flash" not in case


def test_step_program_keeps_its_names_and_scopes(topo, monkeypatch):
    # The flagship's step (flash attention, chunked head, fused AdamW) at
    # a small depth and width, head size 64. The kernels ask
    # jax.default_backend(), which is the CPU here, so they are steered to
    # Mosaic from outside.
    import sys

    for mod in ("flash_attention", "fused_adamw"):
        monkeypatch.setattr(
            sys.modules[f"distributeddeeplearning_tpu.ops.{mod}"],
            "_default_interpret", lambda: False,
        )
    mesh = build_mesh(MeshConfig(dp=1), devices=[topo.devices[0]])
    model = models.get_model(
        "gpt2", size="tiny", num_heads=2, embed_dim=128, vocab_size=512,
        max_len=256, dropout_rate=0.0, attn_impl="flash", chunked_head=True,
    )
    ds = data_lib.SyntheticTokens(
        batch_size=2, seq_len=256, vocab_size=512, seed=0
    )
    trainer = Trainer(
        model, make_optimizer("adamw_fused", 1e-3, weight_decay=0.1),
        get_task("lm"), mesh, donate=False,
    )
    lowered = trainer.lower_train_step(ds.batch(0))
    assert "module @jit_step_fn " in lowered.as_text()
    text = lowered.compile().as_text()
    assert text.startswith("HloModule jit_step_fn")
    # In a model the kernels' names come out whole (no ``attn``, no
    # ``step_fn``): the families of a traced run's device_ops.
    assert _kernel_names(text) == {
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "fused_adamw"
    }
    # Scopes reach op_name only (XLA's own instructions keep their opcode
    # names): an operator's profile splits the step by them.
    ops = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("optimizer", "chunked_xent"):
        assert any(
            re.search(rf"[/(]{scope}[/)]", o) for o in ops
        ), scope
    assert any("/optimizer/fused_adamw" in o for o in ops)


def test_engine_programs_keep_their_names():
    # The names a device trace shows for the engine's runs (its
    # ``XLA Modules`` line) are those of the jitted methods; the backend
    # does not enter into them, so the CPU's lowering pins them.
    from distributeddeeplearning_tpu.config import ServingConfig
    from distributeddeeplearning_tpu.serving import ServingEngine

    model = models.get_model("gpt2", size="tiny", vocab_size=97, max_len=64)
    params = model.init(
        jax.random.PRNGKey(7), np.zeros((1, 8), np.int32)
    )["params"]
    eng = ServingEngine(model, params, ServingConfig(
        slots=2, block_size=4, hbm_budget_mb=8, max_seq_len=48,
        prompt_buckets=(8,), speculation="ngram:2",
    ))
    programs = {
        "_decode_fn": eng._decode_exe_or_compile(),
        "_verify_fn": eng._verify_exe_or_compile(),
        "_prefill_fn": eng._prefill_exe_for(8),
    }
    for name, exe in programs.items():
        text = exe.as_text()
        assert text.startswith(f"HloModule jit_{name}"), text[:80]
        if name != "_verify_fn":  # verify is greedy: it samples nothing
            assert re.search(r'op_name="[^"]*/sample/', text), name
