"""stablelm-1.6b — dense MHA LM with partial rotary embeddings.
[hf:stabilityai/stablelm-2-1_6b] 24L, d_model=2048, 32 heads (MHA, hd=64),
d_ff=5632 SwiGLU, vocab=100352, rotary_pct=0.25.

``chip_config`` is one TPU v5e chip's share (16 GB) of a CADA training
deployment: every published width kept, the vocabulary split over 8 chips
(this chip holds 12,544 rows of the embedding and the head, and the token
stream draws its ids from that slice), and the layers left out standing for
further pipeline stages on other chips. cada2 with M=4 workers keeps about
70 B per parameter on the device (bf16 θ, fp32 h/v̂/∇̄, 4 fp32 worker rows,
5 bf16 ring rows, the step's fp32 gradient temporaries), so two layers and
the vocabulary slice (about 154 M parameters) leave room for activations.
"""
from repro.configs.base import register
from repro.models.config import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="stablelm-1.6b", arch_type="dense", block="dense",
        n_layers=24, d_model=2048, vocab=100352,
        n_heads=32, n_kv_heads=32, d_ff=5632, mlp_act="swiglu",
        rope_theta=1e4, rotary_pct=0.25,
        source="hf:stabilityai/stablelm-2-1_6b",
    )


def smoke_config() -> ModelConfig:
    return config().with_(
        name="stablelm-smoke", n_layers=2, d_model=128, vocab=256,
        n_heads=4, n_kv_heads=4, d_ff=256, dtype="float32", remat=False)


CHIP_VOCAB_SPLIT = 8    # chips sharing the vocabulary in the deployment
CHIP_LAYERS = 2         # layers held here; the rest are pipeline stages


def chip_config() -> ModelConfig:
    """One chip's cut at published widths (see the module docstring)."""
    pub = config()
    return pub.with_(
        name="stablelm-1.6b-chip", n_layers=CHIP_LAYERS,
        vocab=pub.vocab // CHIP_VOCAB_SPLIT,
        reduced=(("n_layers", pub.n_layers), ("vocab", pub.vocab)),
        deployment=(f"vocabulary split over {CHIP_VOCAB_SPLIT} chips; "
                    f"{pub.n_layers - CHIP_LAYERS} of {pub.n_layers} layers "
                    "on further pipeline stages"))


register("stablelm-1.6b", config, smoke_config)
