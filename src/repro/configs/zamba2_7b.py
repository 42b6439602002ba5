"""zamba2-7b [hybrid]: Mamba2 backbone with two shared attention+MLP blocks
applied in turn at the published hybrid layers.  Sub-quadratic =>
long_500k runs.  [arXiv:2411.15242; Zyphra/Zamba2-7B-Instruct
config.json]"""
import jax.numpy as jnp

from repro.configs.base import ArchSpec
from repro.models.lm.model import LMConfig
from repro.models.lm.ssm import SSMConfig

FULL = LMConfig(
    name="zamba2-7b", family="zamba",
    n_layers=81, d_model=3_584, n_heads=32, n_kv_heads=32, head_dim=224,
    d_ff=14_336, vocab=32_000, rope_theta=10_000.0, rms_norm_eps=1e-5,
    ssm=SSMConfig(d_state=64, head_dim=64, expand=2, d_conv=4, chunk=256,
                  n_groups=2),
    shared_attn_every=6,
    hybrid_layer_ids=(6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77),
    num_mem_blocks=2, adapter_rank=128, sub_quadratic=True,
)

SMOKE = LMConfig(
    name="zamba2-smoke", family="zamba",
    n_layers=7, d_model=64, n_heads=4, n_kv_heads=4, head_dim=32, d_ff=128,
    vocab=128, rms_norm_eps=1e-5,
    ssm=SSMConfig(d_state=16, head_dim=16, chunk=32, n_groups=2),
    shared_attn_every=3, hybrid_layer_ids=(2, 5), num_mem_blocks=2,
    adapter_rank=8, sub_quadratic=True, dtype=jnp.float32,
)

SPEC = ArchSpec(
    arch_id="zamba2-7b", lm=FULL, smoke=SMOKE,
    notes=("81 layers, 13 of them hybrid (ids as published).  The "
           "LayerStack (models/lm/layerstack.py) runs the published "
           "hybrid layer: shared block j % 2 over [h ; embedding] with "
           "per-application adapter and linear, then its Mamba2 layer; "
           "FULL.variant(n_layers=6, first_layer=18) is one pipeline "
           "stage (layers 18-23).  The LM runtime (model.py) keeps one "
           "shared attention+MLP block over h every 6 layers.  long_500k "
           "decode state is O(1) in sequence length for the mamba "
           "layers; the shared-attention applications keep per-"
           "application KV caches."),
)
