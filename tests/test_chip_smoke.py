"""CPU rehearsal of ``chip_smoke.py`` (the `on-chip-measurement` guide's
rehearsals 1 and 2): the script itself must FAIL here at its device check,
so the phases are imported and called at a tiny preset — same functions,
same checks, Pallas kernels in interpret mode, and the four-chip phase on
four of the simulated devices. What only the chip's compiler can refuse is
``tests/test_tpu_compile.py``'s; what only the chip can show is the script's.
"""

import json
import math
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import chip_smoke  # noqa: E402

# Depth, width, vocabulary and context cut; flash + chunked head + fused
# AdamW + ZeRO-1 stay as configs/gpt2_owt.py has them.
_TINY_GPT2 = [
    "model.kwargs.size=tiny", "model.kwargs.vocab_size=256",
    "model.kwargs.max_len=64", "data.vocab_size=256", "data.seq_len=64",
]


@pytest.fixture
def compiles():
    log = chip_smoke.CompileLog()
    yield log
    log.close()


def _last_json(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_script_fails_at_the_device_check_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, cwd=_REPO,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_train_phase_tiny(compiles, capsys):
    rec = chip_smoke.phase_train(
        "train_gpt2", chip_smoke.GPT2_CONFIG,
        [*_TINY_GPT2, "data.batch_size=8"], steps=12, warmup_steps=0,
        first_loss=math.log(256), expect_kernels=False, compiles=compiles,
    )
    assert rec == _last_json(capsys)
    assert rec["train_step_compiles"] == 1
    assert rec["losses"][-1] < rec["losses"][0]
    assert rec["peak_bytes_in_use"] is None  # the CPU backend reports none


def test_train_phase_refuses_a_wrong_first_loss(compiles):
    with pytest.raises(RuntimeError, match="first loss"):
        chip_smoke.phase_train(
            "train_gpt2", chip_smoke.GPT2_CONFIG,
            [*_TINY_GPT2, "data.batch_size=8"], steps=2, warmup_steps=0,
            first_loss=math.log(50257), expect_kernels=False,
            compiles=compiles,
        )


def test_serve_phase_tiny(compiles, capsys):
    rec = chip_smoke.phase_serve(
        chip_smoke.GPT2_CONFIG,
        [*_TINY_GPT2, "model.kwargs.attn_impl=xla", "data.batch_size=1",
         "serving.hbm_budget_mb=8",
         "serving.prompt_buckets=[8,16,32]", "serving.block_size=4"],
        prompt_lens=(3, 9, 14, 20), max_new_tokens=16,
        kernels=("gather", "pallas"), tol=chip_smoke.SERVE_LOGIT_TOL,
        seed=0, expect_kernels=False, compiles=compiles,
    )
    assert rec == _last_json(capsys)
    for kernel in ("gather", "pallas"):
        assert rec["kernels"][kernel]["tokens"] == 4 * 16
        assert rec["kernels"][kernel]["worst_logit_gap"] <= rec[
            "kernels"][kernel]["logit_tol"]


def test_kernels_phase_tiny(compiles, capsys):
    rec = chip_smoke.phase_kernels(
        heads=4, head_dim=64, seq=256, pool_blocks=32, vocab=1000, embed=64,
        llama_kwargs=dict(size="tiny", vocab_size=256, max_len=128),
        interpret=None, seed=0, compiles=compiles,
    )
    assert rec == _last_json(capsys)
    assert set(rec["max_abs_err"]) == {
        "flash_fwd", "ring_pallas_cp1", "paged_fp_mha", "paged_int8_mha",
        "paged_fp_gqa", "paged_int8_gqa", "fused_adamw",
    }
    assert 0 < rec["flash_bwd_rel_err"] < chip_smoke.FLASH_BWD_REL_TOL


def test_flash_backward_check_refuses_zeroed_and_misscaled_gradients():
    # At GPT-2 widths a mean loss makes every gradient entry ~1e-6: the
    # check must be relative, or a backward kernel that returns zeros passes.
    import jax
    import jax.numpy as jnp

    ref = [
        1e-6 * jax.random.normal(k, (2, 64, 4, 64)).astype(jnp.bfloat16)
        for k in jax.random.split(jax.random.PRNGKey(0), 3)
    ]
    assert chip_smoke.check_flash_backward(ref, ref) == 0.0
    zeroed = [ref[0], jnp.zeros_like(ref[1]), ref[2]]
    with pytest.raises(RuntimeError, match="flash bwd"):
        chip_smoke.check_flash_backward(zeroed, ref)
    with pytest.raises(RuntimeError, match="flash bwd"):
        chip_smoke.check_flash_backward([g * 1.125 for g in ref], ref)


def test_four_chip_phase_on_four_simulated_devices(compiles, capsys):
    rec = chip_smoke.phase_dp(
        chip_smoke.GPT2_CONFIG, [*_TINY_GPT2, "data.batch_size=8"], steps=3,
        n_chips=4, loss_tol=chip_smoke.DP_LOSS_TOL, first_loss=math.log(256),
        expect_kernels=False, compiles=compiles,
    )
    assert rec == _last_json(capsys)
    assert rec["phase"] == "train_gpt2_dp4"
    full, shard = rec["zero1_full_shape"], rec["zero1_shard_shape"]
    assert math.prod(shard) * 4 == math.prod(full)
    assert rec["collectives"]["all-gather"] > 0
