"""granite-4.0-h-micro [hybrid] — 40L d_model=2048, Mamba-2 mixers with
one GQA attention layer in every period of 10 (positions 5, 15, 25, 35),
a SwiGLU MLP after every mixer, vocab=100352, tied embeddings.

Mamba-2: 64 heads of 64 (expand 2, d_inner 4096), d_state 128, one
B/C group, conv width 4 with bias, chunk 256.  Attention: 32 query
heads, 8 KV heads, head dim 64, no position embedding (NoPE), score
scale ``attention_multiplier`` 1/64.  MLP: one fused input projection
[2048, 2 x 8192] (gate | up).  RMSNorm eps 1e-5.  Scale factors:
embeddings x12, each residual branch x0.22, logits / 8.

[https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json]
"""
import dataclasses

from repro.configs.base import ModelConfig

SOURCE = ("https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/"
          "main/config.json")
PERIOD = 10
#: the published ``layer_types``: attention at 5, 15, 25, 35
LAYER_TYPES = tuple("attention" if i % PERIOD == 5 else "mamba"
                    for i in range(40))


def config() -> ModelConfig:
    return ModelConfig(
        arch_id="granite-4.0-h-micro",
        family="hybrid",
        n_layers=40,
        d_model=2048,
        vocab_size=100352,
        n_heads=32,
        n_kv_heads=8,
        head_dim=64,
        position_embedding="nope",
        attention_multiplier=0.015625,
        d_ff=8192,
        activation="silu",
        fused_gate_up=True,
        tie_embeddings=True,
        norm="rmsnorm",
        norm_eps=1e-5,
        embedding_multiplier=12.0,
        residual_multiplier=0.22,
        logits_scaling=8.0,
        layer_types=LAYER_TYPES,
        ssm_state=128,
        ssm_expand=2,
        ssm_head_dim=64,
        ssm_conv_width=4,
        ssm_chunk=256,
        ssm_n_groups=1,
        ssm_conv_bias=True,
        source=f"[{SOURCE}]",
    )


def first_layers(n: int, cfg: ModelConfig = None) -> ModelConfig:
    """The first ``n`` layers of ``layer_types``, in their order (``n``
    = 10: one whole period, 9 Mamba-2 layers and 1 attention layer)."""
    cfg = cfg or config()
    if not 1 <= n <= len(cfg.layer_types):
        raise ValueError(f"n={n} not in 1..{len(cfg.layer_types)}")
    return dataclasses.replace(cfg, n_layers=n,
                               layer_types=cfg.layer_types[:n])
