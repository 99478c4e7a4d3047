"""Model FLOPs accounting for MFU/HFU reporting.

The reference publishes MFU/HFU per the PaLM appendix-B convention
(ref:README.md:22-30). Same convention here:

- matmul params contribute 2 FLOPs/param/token forward (embedding gather
  contributes none; the lm_head matmul counts);
- causal attention contributes 2 * S * d_attn FLOPs/token/layer forward
  (QK^T and PV, halved for causality);
- backward = 2x forward; train = 3x forward;
- HFU additionally counts recomputed forward FLOPs for remat'ed blocks.
"""

from fms_fsdp_tpu.models.configs import LlamaConfig, MixtralConfig


def llama_matmul_params(cfg: LlamaConfig) -> int:
    """Params participating in matmuls (everything but the embedding table)."""
    return cfg.n_params(include_embeddings=False) + cfg.src_vocab_size * cfg.emb_dim


def llama_fwd_flops_per_token(cfg: LlamaConfig, seq_len: int) -> float:
    mm = 2 * llama_matmul_params(cfg)
    attn_dim = cfg.nheads * cfg.head_dim
    attn = cfg.nlayers * 2 * seq_len * attn_dim  # causal: S/2 keys avg, x4
    return mm + attn


def llama_train_flops_per_token(
    cfg: LlamaConfig, seq_len: int, ac_fraction: float = 0.0
) -> float:
    """Model FLOPs (MFU numerator) per token for fwd+bwd.

    ``ac_fraction`` > 0 gives the HFU numerator: remat'ed blocks replay
    their forward in the backward pass.
    """
    fwd = llama_fwd_flops_per_token(cfg, seq_len)
    return fwd * (3 + ac_fraction)


def mamba_matmul_params(cfg) -> int:
    """Matmul-participating params of the hybrid Mamba2 stack (everything
    but the embedding gather; lm_head counts). Mirrors
    models/mamba.py:init_mamba_params layer shapes."""
    d = cfg.d_model
    ipd = 2 * cfg.d_inner + 2 * cfg.ngroups * cfg.d_state + cfg.nheads
    a = cfg.attn_cfg
    total = d * cfg.padded_vocab_size  # lm_head
    for i in range(cfg.n_layer):
        if i in cfg.attn_layer_idx:
            total += d * (a.num_heads + 2 * a.num_heads_kv) * a.head_dim
            total += a.num_heads * a.head_dim * d
        else:
            total += d * ipd + cfg.d_inner * d
        if cfg.d_intermediate > 0:
            total += 3 * d * cfg.d_intermediate
    return total


def mamba_fwd_flops_per_token(cfg, seq_len: int) -> float:
    """Forward FLOPs/token: matmuls + the chunked SSD scan + conv1d +
    the hybrid attention layers (causal convention as in the Llama
    accounting)."""
    mm = 2 * mamba_matmul_params(cfg)
    L = min(cfg.chunk_size, seq_len)  # ssd_scan clamps the chunk the same way
    G, N = cfg.ngroups, cfg.d_state
    H, P = cfg.nheads, cfg.headdim
    n_mamba = cfg.n_layer - len(cfg.attn_layer_idx)
    # per token per mamba layer: CB (2*L*G*N), intra y (2*L*H*P),
    # states + inter-chunk output (4*N*H*P each pair)
    scan = n_mamba * (2 * L * G * N + 2 * L * H * P + 4 * N * H * P)
    conv = n_mamba * 2 * (cfg.d_inner + 2 * G * N) * cfg.d_conv
    a = cfg.attn_cfg
    attn = len(cfg.attn_layer_idx) * 2 * seq_len * a.num_heads * a.head_dim
    return mm + scan + conv + attn


def mamba_train_flops_per_token(cfg, seq_len: int, ac_fraction: float = 0.0):
    return mamba_fwd_flops_per_token(cfg, seq_len) * (3 + ac_fraction)


def mixtral_matmul_params_active(cfg) -> int:
    """Matmul params a token actually touches: dense attention + router +
    the ``top_k`` activated expert FFNs + lm_head. The standard MoE MFU
    convention counts activated FLOPs only — capacity slack
    (capacity_factor > top_k buffer fill) and dispatch movement are real
    work that does NOT count toward the numerator."""
    d, h = cfg.emb_dim, cfg.hidden_dim
    attn_dim = cfg.nheads * cfg.head_dim
    kv_dim = cfg.n_kv_heads * cfg.head_dim
    per_layer = (
        d * attn_dim  # wq
        + 2 * d * kv_dim  # wk, wv
        + attn_dim * d  # wo
        + d * cfg.num_experts  # router gate
        + cfg.top_k * 3 * d * h  # activated expert SwiGLU
    )
    return cfg.nlayers * per_layer + cfg.src_vocab_size * d  # + lm_head


def mixtral_fwd_flops_per_token(cfg, seq_len: int) -> float:
    mm = 2 * mixtral_matmul_params_active(cfg)
    attn = cfg.nlayers * 2 * seq_len * cfg.nheads * cfg.head_dim
    return mm + attn


def mixtral_train_flops_per_token(cfg, seq_len: int, ac_fraction: float = 0.0):
    return mixtral_fwd_flops_per_token(cfg, seq_len) * (3 + ac_fraction)


def train_flops_per_token(model_cfg, seq_len: int, ac_fraction: float = 0.0):
    """Family dispatch for MFU/HFU accounting."""
    if isinstance(model_cfg, LlamaConfig):
        return llama_train_flops_per_token(model_cfg, seq_len, ac_fraction)
    if isinstance(model_cfg, MixtralConfig):
        return mixtral_train_flops_per_token(model_cfg, seq_len, ac_fraction)
    return mamba_train_flops_per_token(model_cfg, seq_len, ac_fraction)


# Published per-chip peaks: dense bf16 FLOP/s and HBM bytes/s. Source:
# Google Cloud TPU documentation, the system-architecture page of each
# generation ("TPU v5e": 197 TFLOPS bf16, 819 GBps; "TPU v5p": 459,
# 2765; "TPU v6e": 918, 1640; "TPU v4": 275, 1200). ``kinds`` are the
# substrings of jax's ``device_kind`` that name the chip, matched in
# this order ("v5" alone is a v5p, so v5e comes first).
CHIP_PEAKS = {
    "v5e": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
            "kinds": ("v5 lite", "v5e")},
    "v5p": {"flops": 459e12, "hbm_bytes_per_s": 2765e9,
            "kinds": ("v5p", "v5")},
    "v6e": {"flops": 918e12, "hbm_bytes_per_s": 1640e9,
            "kinds": ("v6 lite", "v6e")},
    "v4": {"flops": 275e12, "hbm_bytes_per_s": 1200e9, "kinds": ("v4",)},
}


def device_info():
    """The device a record was measured on, as jax reports it — the
    three keys every result in this repo names its hardware by."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def chip_from_device_kind(kind: str):
    """``CHIP_PEAKS`` key for a jax ``device_kind`` (or an
    ``obs_chip_hint`` such as "v5e"); None when the table has no row."""
    kind = kind.lower()
    for chip, row in CHIP_PEAKS.items():
        if any(k in kind for k in row["kinds"]):
            return chip
    return None


def chip_peaks(kind_hint: str = ""):
    """The peaks row MFU and roofline shares divide by: the attached
    device's, or the row ``kind_hint`` (TrainConfig.obs_chip_hint)
    names instead. None on a non-TPU backend — a CPU record carries no
    utilization against a TPU peak. A TPU the table does not know is an
    error, not a default."""
    dev = device_info()
    if dev["platform"] != "tpu":
        return None
    kind = kind_hint or dev["kind"]
    chip = chip_from_device_kind(kind)
    if chip is None:
        raise ValueError(
            f"no published peaks for device kind {kind!r}: add a row "
            f"(with its source) to utils/flops.py::CHIP_PEAKS, or name "
            f"a known chip in obs_chip_hint {sorted(CHIP_PEAKS)}"
        )
    return CHIP_PEAKS[chip]


def peak_flops_per_chip(kind_hint: str = ""):
    row = chip_peaks(kind_hint)
    return None if row is None else row["flops"]
