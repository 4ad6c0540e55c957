"""mamba2-2.7b [ssm] — SSD (state-space duality), attention-free.

Source: https://huggingface.co/state-spaces/mamba2-2.7b (``config.json``:
64 layers, d_model 2560, no MLP, rms_norm, residual_in_fp32, tied
embeddings, vocab_size 50277 with pad_vocab_size_multiple 16) and
mamba_ssm's ``Mamba2`` defaults for what ``config.json`` leaves out:
d_state 128, d_conv 4 with a bias over x|B|C, expand 2, headdim 64,
ngroups 1, no projection bias, gated RMSNorm (norm_before_gate false),
eps 1e-5 (arXiv:2405.21060).

``vocab_size`` is the embedding's row count: the tokenizer's 50277 padded
to a multiple of 16, as ``MambaLMHeadModel`` pads it.  ``ssm_chunk`` is
128, the serving kernel's chunk; the published 256 is training's, and the
chunked scan is exact for any chunk.
"""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="mamba2-2.7b",
    family="ssm",
    n_layers=64,
    d_model=2560,
    n_heads=0,
    n_kv_heads=0,
    d_ff=0,
    vocab_size=50288,
    ssm_state=128,
    ssm_head_dim=64,
    ssm_expand=2,
    ssm_conv=4,
    ssm_chunk=128,
    norm_eps=1e-5,
    residual_f32=True,
    tie_embeddings=True,
    sub_quadratic=True,        # attention-free: runs long_500k
    source="https://huggingface.co/state-spaces/mamba2-2.7b",
)
