"""Command A+ (``cohere2_moe``, 218B-A25B) — window and NoPE-global
attention layers 3:1, 128 query heads on 8 KV heads, 128 sigmoid-routed
experts top-8 with four averaged shared ones, a parallel block
(``models/cohere2_moe.py``).

The published widths are the model's defaults (huggingface.co/CohereLabs/
command-a-plus-05-2026 ``config.json``); weights and compute are bfloat16
as the checkpoint states. Whole, one layer's 128 experts are 12.9 GB: no
chip holds a layer. One chip serves its share of a deployment in which
eight chips divide each layer (``benchmarks/configs/command_a_plus.json``):
``--override "model.kwargs.layer_types=('sliding_attention', 'sliding_attention', 'sliding_attention', 'full_attention')"``
(one period), ``--override "model.kwargs.held_experts=(0,16)"`` (16 of the
128 routed experts; the router keeps its 128 outputs),
``--override model.kwargs.vocab_size=32768``: 9.5 GB in bfloat16. At a
test's size: ``--override model.kwargs.size=tiny``.

Serving: ``cli serve`` through ``ServingEngine`` on two kinds of paged
cache side by side, every token for the global layers and a window layer's
window and no more (docs/SERVING.md). Training the family is not asked for
and not tested (``models/cohere2_moe.py``).
"""

from distributeddeeplearning_tpu.config import (
    Config,
    DataConfig,
    ModelConfig,
    OptimConfig,
    ServingConfig,
    TrainConfig,
)
from distributeddeeplearning_tpu.mesh import MeshConfig


def get_config() -> Config:
    return Config(
        model=ModelConfig(
            name="cohere2_moe",
            kwargs={
                "size": "a_plus",
                "attn_impl": "xla",
                "dtype": "bfloat16",
                "param_dtype": "bfloat16",
            },
        ),
        data=DataConfig(
            kind="synthetic_tokens", batch_size=1, seq_len=128,
            vocab_size=262144,
        ),
        optim=OptimConfig(
            name="adamw", lr=3e-4, b2=0.95, weight_decay=0.1,
            schedule="cosine", warmup_steps=200, grad_clip=1.0,
        ),
        train=TrainConfig(steps=1000, log_every=20, task="lm"),
        mesh=MeshConfig(dp=-1),
        serving=ServingConfig(
            slots=16, block_size=16, max_seq_len=13312,
            prompt_buckets=(2048, 4096, 8192, 12288), hbm_budget_mb=1664,
        ),
    )
