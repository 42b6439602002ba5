"""A tiny cell for the CPU tests: the benchmark's dense configuration at
a few hundredths of its widths, with a sliding window shorter than the
sequence, float32, on the program's reference path."""
from chipbench.run import Cell

DENSE = {
    "hidden_size": 64, "vocab_size": 128, "intermediate_size": 96,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "hidden_act": "silu", "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "sliding_window": 12, "dtype": "float32",
    "layers": ["embed", "attn", "attn", "head"],
    "program": {"preset": "repro.configs.phi3_medium_14b:SMOKE",
                "overrides": {"n_layers": 2, "d_model": 64, "n_heads": 4,
                              "n_kv_heads": 2, "head_dim": 16, "d_ff": 96,
                              "vocab": 128, "sliding_window": 12,
                              "dtype": "float32"}},
}

PLANNED = {"seq_len": 32, "batch": 2,
           "fleet": {"preset": "lm_default", "args": {"m": 2}},
           "schedule": "planned", "lr": 1e-2, "backend": "ref"}

SPLIT = dict(PLANNED, schedule={
    "worker_o": "cloud", "worker_l": "edge",
    "s_workers": ["device_0", "device_1"], "m_s": [3, 0], "m_l": 3,
    "b_o": 1, "b_s": [1, 0], "b_l": 0})

# Float32 on both sides: the program and the reference agree to
# round-off, far inside these.
LIMITS = {"loss": 1e-4, "grad": 1e-3, "change": 1e-3}


def cell(config=DENSE, mix=PLANNED, name="tiny", chips=1) -> Cell:
    return Cell(name=name, chips=chips, config=config, mix=mix,
                limits=LIMITS, per_layer=[])
