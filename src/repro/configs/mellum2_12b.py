"""mellum2-12b: Mellum2-12B-A2.5B-Instruct [hf:JetBrains/Mellum2-12B-A2.5B-Instruct
config.json]: 64-expert top-8 MoE in every layer, no shared expert; layers in
periods of three sliding-window (1024) and one full attention layer; YaRN
(x16 over 8192) on the full layers only.

Conventions the config does not state follow the Qwen2/3-MoE keys it uses:
a float32 softmax router over all experts, top-8 renormalized
(``norm_topk_prob``), no QK-norm; the MTP head has no keys and is not
served.  ``intermediate_size`` (7168) is the width of a dense layer, which
the model has none of; ``moe_intermediate_size`` is ``d_expert``."""
from repro.configs.base import FULL, SLIDING, ModelConfig, YaRN

_PERIOD = (SLIDING, SLIDING, SLIDING, FULL)

CONFIG = ModelConfig(
    arch="mellum2-12b", family="moe", n_layers=28, d_model=2304, n_heads=32,
    n_kv_heads=4, d_ff=7168, vocab=98304, head_dim=128, rope_theta=500000.0,
    norm_eps=1e-6, n_experts=64, experts_per_token=8, d_expert=896,
    layer_types=_PERIOD * 7, sliding_window=1024,
    rope_yarn=YaRN(factor=16.0, original_max_position_embeddings=8192,
                   beta_fast=32.0, beta_slow=1.0,
                   attention_factor=1.2772588722239782),
)

SMOKE = ModelConfig(
    arch="mellum2-smoke", family="moe", n_layers=4, d_model=64, n_heads=4,
    n_kv_heads=2, d_ff=128, vocab=256, head_dim=16, rope_theta=500000.0,
    norm_eps=1e-6, n_experts=8, experts_per_token=2, d_expert=32,
    layer_types=_PERIOD, sliding_window=8, kv_page_size=4,
    rope_yarn=YaRN(factor=16.0, original_max_position_embeddings=16,
                   beta_fast=32.0, beta_slow=1.0),
    vocab_pad_multiple=64, dtype="float32",
)
