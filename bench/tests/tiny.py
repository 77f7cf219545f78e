"""A tiny configuration and mixes that drive the whole harness on the
CPU (test sizes only; no benchmark cell uses them)."""
import copy
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

SHAPE = {"model_type": "qwen2", "hidden_size": 64, "intermediate_size": 128,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "num_hidden_layers": 2, "vocab_size": 512, "rms_norm_eps": 1e-6,
         "rope_theta": 1e6, "tie_word_embeddings": False}
CONF = {**SHAPE,
        "draft": {**SHAPE, "hidden_size": 32, "intermediate_size": 64,
                  "num_attention_heads": 2, "num_key_value_heads": 1,
                  "tie_word_embeddings": True},
        "serving": {"slots": 4, "max_len": 96,
                    "n_stages": 4, "width": 8, "branch": 4},
        "limits": {"target_kl": 1e-7, "draft_kl": 1e-7}}
CLOSED = {"loop": "closed", "requests": 12,
          "prompt_tokens": {"median": 16, "sigma": 0.8, "min": 8, "max": 32},
          "output_tokens": {"median": 6, "sigma": 0.5, "min": 4, "max": 8}}
OPEN = {**CLOSED, "loop": "open", "rate_per_s": 2.0,
        "in_flight_at_open": 2}
BENCHMARK = {"end_to_end": [{"name": n, "unit": "x"} for n in
                            ("tokens_per_s", "setup_s")],
             "per_layer": [{"name": n, "unit": "x"} for n in
                           ("tokens_per_timestep", "token_gap_p95_ms",
                            "timestep_ms",
                            "dispatches_per_timestep", "step_mfu")]}
PEAK = {"bf16_flops": 1e12, "hbm_bytes": 1e11}


def conf():
    return copy.deepcopy(CONF)


def run(mix, seed=3, seconds=3.0, trace=False, conf_=None, control=False):
    """One CPU run of the harness (the chip check and the peaks table,
    both of which need a TPU, are stood in for)."""
    import jax

    import flops
    import run as bench_run
    cell = {"name": "tiny", "chips": 1}
    real = flops.peaks
    flops.peaks = lambda kind: PEAK
    try:
        return bench_run.run_cell(BENCHMARK, cell, conf_ or conf(), mix,
                                  seed, seconds, trace, jax.devices()[:1],
                                  control=control)
    finally:
        flops.peaks = real
