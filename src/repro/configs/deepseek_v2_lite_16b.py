"""deepseek-v2-lite-16b [moe]: 27L d_model=2048 16H (MLA kv_lora=512, no q
compression, YaRN rope x40) per-expert d_ff=1408 vocab=102400, 64 routed
experts top-6 (softmax, gates not renormalised, per-sequence aux loss
alpha 0.001) + 2 shared, first layer dense MLP (d_ff=10944), RMSNorm eps
1e-6, untied head [arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2-Lite]."""
from repro.configs.base import ArchDef
from repro.models.attention import MLASpec
from repro.models.layers import YarnSpec
from repro.models.lm import LMConfig
from repro.models.moe import MoESpec

#: rope_scaling of the published config.json
YARN = YarnSpec(factor=40.0, original_max_position=4096, beta_fast=32.0, beta_slow=1.0,
                mscale=0.707, mscale_all_dim=0.707)


def _full() -> LMConfig:
    return LMConfig(
        name="deepseek-v2-lite-16b", d_model=2048, vocab=102400, n_layers=27,
        prefix=(("mla", "swiglu"),),          # layer 0: dense MLP
        pattern_unit=(("mla", "moe"),), n_units=26,
        mla=MLASpec(n_heads=16, kv_lora_rank=512, qk_nope_dim=128,
                    qk_rope_dim=64, v_head_dim=128, rope_theta=10000.0,
                    rope_scaling=YARN),
        moe=MoESpec(n_experts=64, top_k=6, d_ff=1408, n_shared=2, shared_d_ff=1408,
                    norm_topk=False, aux_alpha=0.001, seq_aux=True),
        d_ff=10944,
    )


def reduced() -> LMConfig:
    """Every mechanism of the full config at tiny widths: YaRN rope on
    interleaved pairs, the kv norm, unnormalised top-k gates, two shared
    experts, the dense first layer and the per-sequence aux loss."""
    return LMConfig(
        name="deepseek-v2-lite-16b-reduced", d_model=64, vocab=512, n_layers=3,
        prefix=(("mla", "swiglu"),),
        pattern_unit=(("mla", "moe"),), n_units=2,
        mla=MLASpec(n_heads=4, kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
                    v_head_dim=16, rope_theta=10000.0, rope_scaling=YARN),
        moe=MoESpec(n_experts=8, top_k=2, d_ff=48, n_shared=2, shared_d_ff=48,
                    capacity_factor=4.0, norm_topk=False, aux_alpha=0.001, seq_aux=True),
        d_ff=160, remat=False,
    )


ARCH = ArchDef("deepseek-v2-lite-16b", "moe", _full(), reduced, "arXiv:2405.04434")
