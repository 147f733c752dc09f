"""GLM-4.7-Flash (``glm4_moe_lite``, 30B-A3B) — the latent-attention,
sigmoid-routed expert decoder (``models/glm4_moe_lite.py``).

The published widths are the model's defaults (huggingface.co/zai-org/
GLM-4.7-Flash ``config.json``); weights and compute are bfloat16 as the
checkpoint states, and parameters are created in that dtype. Whole, the
model is 30B parameters: one chip serves a cut of its depth
(``--override model.kwargs.num_layers=6``: the leading dense layer and five
expert layers, 7.8 GB in bfloat16, every expert of each layer resident;
``benchmarks/configs/glm47_flash.json`` is that deployment). At a test's
size: ``--override model.kwargs.size=tiny``.

Serving: ``cli serve`` through ``ServingEngine`` on the latent paged cache
(docs/SERVING.md). Training runs the same blocks on one data-parallel mesh
(the ``ep`` axis is refused: an expert layer told which experts it holds
is not built), with plain AdamW on float32 masters
(``train.precision.policy=bf16`` would keep them; this file trains in the
checkpoint's dtype to stay inside one chip at a small depth).
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
            name="glm4_moe_lite",
            kwargs={
                "size": "flash",
                "max_len": 8192,
                "attn_impl": "xla",
                "chunked_head": True,
                "dtype": "bfloat16",
                "param_dtype": "bfloat16",
            },
        ),
        data=DataConfig(
            kind="synthetic_tokens", batch_size=1, seq_len=128,
            vocab_size=154880,
        ),
        optim=OptimConfig(
            name="adamw", lr=3e-4, b2=0.95, weight_decay=0.1,
            schedule="cosine", warmup_steps=200, grad_clip=1.0,
        ),
        train=TrainConfig(steps=1000, log_every=20, task="lm"),
        mesh=MeshConfig(dp=-1),
        serving=ServingConfig(
            slots=64, block_size=16, max_seq_len=6144,
            prompt_buckets=(1024, 2048, 4096), hbm_budget_mb=2048,
        ),
    )
