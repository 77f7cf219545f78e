import pytest

from repro.models.config import (EncoderConfig, MLAConfig, ModelConfig,
                                 MoEConfig, RGLRUConfig, SSMConfig)

# CPU tests must see exactly ONE device (the dry-run sets its own 512-device
# flag in its own process) — nothing to configure here on purpose.


@pytest.fixture(scope="session")
def tiny_dense():
    return ModelConfig(name="t-dense", family="dense", num_layers=3,
                       d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                       vocab_size=128)


@pytest.fixture(scope="session")
def tiny_draft():
    return ModelConfig(name="t-draft", family="dense", num_layers=1,
                       d_model=32, num_heads=2, num_kv_heads=1, d_ff=64,
                       vocab_size=128)


@pytest.fixture(scope="session")
def tiny_moe():
    return ModelConfig(
        name="t-moe", family="moe", num_layers=2, d_model=64, num_heads=4,
        num_kv_heads=2, d_ff=128, vocab_size=128,
        moe=MoEConfig(num_experts=4, experts_per_token=2, d_ff_expert=64,
                      num_shared_experts=1, first_dense=1,
                      capacity_factor=8.0))


@pytest.fixture(scope="session")
def tiny_mla():
    return ModelConfig(
        name="t-mla", family="dense", num_layers=2, d_model=64, num_heads=4,
        num_kv_heads=4, d_ff=128, vocab_size=128,
        mla=MLAConfig(kv_lora_rank=32, q_lora_rank=24, qk_nope_head_dim=16,
                      qk_rope_head_dim=8, v_head_dim=16))


@pytest.fixture(scope="session")
def tiny_ssm():
    return ModelConfig(
        name="t-ssm", family="ssm", num_layers=2, d_model=64, num_heads=1,
        num_kv_heads=1, d_ff=0, vocab_size=128,
        ssm=SSMConfig(d_state=16, head_dim=16, chunk=8))


@pytest.fixture(scope="session")
def tiny_hybrid():
    return ModelConfig(
        name="t-hyb", family="hybrid", num_layers=5, d_model=64, num_heads=4,
        num_kv_heads=1, d_ff=128, vocab_size=128,
        rglru=RGLRUConfig(lru_width=64, window=8, pattern="rra"))


@pytest.fixture(scope="session")
def tiny_hybrid_ssm():
    """Jamba-style attn+ssm hybrid (pattern 's' = Mamba-2 SSD sub-layer):
    the recycled-KV-arena regression config — its SSD prefill must seed
    from the zero state, never a previous slot occupant's."""
    return ModelConfig(
        name="t-hyb-ssm", family="hybrid", num_layers=2, d_model=64,
        num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=128,
        ssm=SSMConfig(d_state=16, head_dim=16, chunk=8),
        rglru=RGLRUConfig(pattern="sa", window=0))
