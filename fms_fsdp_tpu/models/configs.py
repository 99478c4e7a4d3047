"""Model architecture configs.

``LlamaConfig`` carries the same architectural degrees of freedom the
reference exercises through fms's ``LLaMAConfig`` (variant table at
ref:fms_fsdp/utils/config_utils.py:25-161): emb_dim, nheads, kvheads (GQA),
nlayers, hidden_grow_factor + multiple_of (SwiGLU width rounding),
max_expected_seq_len, rope_theta, vocab size.

``MambaConfig`` mirrors the mamba_9.8b dict config
(ref:fms_fsdp/utils/config_utils.py:162-185): Mamba2 layers with a few
interleaved attention layers, RMSNorm, residual in fp32. With
``ssm_layer="Mamba1"`` the same stack carries the Mamba-1 selective-scan
mixer of the Jamba hybrids (models/mamba.py).

``MixtralConfig`` covers the sparse-MoE Llama family the reference touches
only as a frozen speculator base (ref:speculator/train_speculator_utils.py:
500-569); here it is additionally a first-class trainable family with
capacity-based routing and expert parallelism (models/mixtral.py).
"""

import dataclasses
import math
from dataclasses import dataclass, field
from typing import Optional, Sequence, Tuple


@dataclass(frozen=True)
class LlamaConfig:
    src_vocab_size: int = 32000
    emb_dim: int = 4096
    norm_eps: float = 1e-5
    nheads: int = 32
    kvheads: int = 0  # 0 -> MHA (kvheads = nheads), else GQA group count
    nlayers: int = 32
    hidden_grow_factor: float = 8 / 3
    multiple_of: int = 256
    max_expected_seq_len: int = 4096
    rope_theta: float = 10000.0
    p_dropout: float = 0.0

    @property
    def head_dim(self) -> int:
        return self.emb_dim // self.nheads

    @property
    def n_kv_heads(self) -> int:
        return self.kvheads if self.kvheads else self.nheads

    @property
    def hidden_dim(self) -> int:
        """SwiGLU inner width with multiple_of rounding (fms GatedLinearUnit)."""
        hidden = int(self.emb_dim * self.hidden_grow_factor)
        if self.multiple_of:
            hidden = self.multiple_of * (
                (hidden + self.multiple_of - 1) // self.multiple_of
            )
        return hidden

    def n_params(self, include_embeddings: bool = True) -> int:
        """Exact parameter count (untied input/output embeddings)."""
        d, h = self.emb_dim, self.hidden_dim
        kv_dim = self.n_kv_heads * self.head_dim
        per_layer = (
            d * d  # wq
            + 2 * d * kv_dim  # wk, wv
            + d * d  # wo
            + 3 * d * h  # w1 and w3 (d->h each), w2 (h->d)
            + 2 * d  # attn norm + ffn norm
        )
        total = self.nlayers * per_layer + d  # final norm
        if include_embeddings:
            total += 2 * self.src_vocab_size * d  # embed + lm head
        return int(total)


@dataclass(frozen=True)
class MambaAttnConfig:
    """Attention sub-config for hybrid Mamba (ref:config_utils.py:170-179)."""

    causal: bool = True
    d_conv: int = 0
    head_dim: int = 128
    num_heads: int = 32
    num_heads_kv: int = 8
    out_proj_bias: bool = False
    qkv_proj_bias: bool = False
    rotary_emb_dim: int = 64


@dataclass(frozen=True)
class MambaConfig:
    d_model: int = 4096
    d_intermediate: int = 14336  # MLP width; 0 -> no MLP block
    n_layer: int = 32
    vocab_size: int = 128256
    ssm_layer: str = "Mamba2"
    attn_layer_idx: Tuple[int, ...] = ()
    attn_cfg: MambaAttnConfig = field(default_factory=MambaAttnConfig)
    rms_norm: bool = True
    residual_in_fp32: bool = True
    fused_add_norm: bool = True
    pad_vocab_size_multiple: int = 16
    tie_embeddings: bool = False
    norm_eps: float = 1e-5
    # mixer hyperparameters (mamba_ssm defaults); headdim, ngroups and
    # chunk_size are Mamba2's alone
    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    headdim: int = 64
    ngroups: int = 1
    chunk_size: int = 256
    # Mamba1 alone: width of the low-rank dt projection; 0 -> the
    # mamba_ssm "auto", ceil(d_model / 16)
    dt_rank: int = 0

    def __post_init__(self):
        if self.ssm_layer not in ("Mamba1", "Mamba2"):
            raise ValueError(
                f"unknown ssm_layer {self.ssm_layer!r}: the hybrid stack "
                f"has a 'Mamba1' (selective scan) and a 'Mamba2' (SSD) "
                f"mixer"
            )

    @property
    def mamba1(self) -> bool:
        return self.ssm_layer == "Mamba1"

    @property
    def dt_rank_(self) -> int:
        return self.dt_rank or -(-self.d_model // 16)

    @property
    def padded_vocab_size(self) -> int:
        m = self.pad_vocab_size_multiple
        return m * ((self.vocab_size + m - 1) // m)

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def nheads(self) -> int:
        return self.d_inner // self.headdim

    def n_params(self) -> int:
        """Exact parameter count of the hybrid stack (see models/mamba.py)."""
        d = self.d_model
        if self.mamba1:
            di, N, R = self.d_inner, self.d_state, self.dt_rank_
            per_mamba = (
                d * 2 * di  # in_proj (u | z)
                + di * (self.d_conv + 1)  # conv weight + bias
                + di * (R + 2 * N)  # x_proj
                + R + 2 * N  # norms on dt, B and C
                + R * di + di  # dt_proj + bias
                + di * N + di  # A_log, D
                + di * d  # out_proj
            )
        else:
            conv_dim = self.d_inner + 2 * self.ngroups * self.d_state
            in_proj = (
                2 * self.d_inner + 2 * self.ngroups * self.d_state
                + self.nheads
            )
            per_mamba = (
                d * in_proj
                + conv_dim * (self.d_conv + 1)  # conv weight + bias
                + 3 * self.nheads  # dt_bias, A_log, D
                + self.d_inner  # gated norm
                + self.d_inner * d  # out_proj
            )
        a = self.attn_cfg
        per_attn = d * a.head_dim * (a.num_heads * 2 + a.num_heads_kv * 2)
        per_mlp = 3 * d * self.d_intermediate + d if self.d_intermediate else 0
        n_attn = len(self.attn_layer_idx)
        total = (
            (self.n_layer - n_attn) * per_mamba
            + n_attn * per_attn
            + self.n_layer * (per_mlp + d)  # mlp (+norm2) and mixer norm
            + d  # final norm
            # embedding, and the head unless it is the embedding
            + (1 if self.tie_embeddings else 2) * self.padded_vocab_size * d
        )
        return int(total)


def jamba_config(d: dict) -> MambaConfig:
    """A published Jamba ``config.json`` (``model_type: jamba``) as the
    hybrid stack's config: Mamba-1 mixers, attention with no positional
    embedding on layers ``i % attn_layer_period == attn_layer_offset``,
    a dense MLP after every mixer, tied head. Only dense checkpoints
    (``num_experts == 1``): the stack has no expert layer."""
    if d.get("num_experts", 1) != 1:
        raise ValueError(
            f"jamba config has num_experts={d['num_experts']}: the hybrid "
            f"stack's feed-forward is a dense MLP (ROADMAP A3)"
        )
    n = d["num_hidden_layers"]
    nq = d["num_attention_heads"]
    return MambaConfig(
        d_model=d["hidden_size"],
        d_intermediate=d["intermediate_size"],
        n_layer=n,
        vocab_size=d["vocab_size"],
        ssm_layer="Mamba1",
        attn_layer_idx=tuple(
            i for i in range(n)
            if i % d["attn_layer_period"] == d["attn_layer_offset"]
        ),
        attn_cfg=MambaAttnConfig(
            head_dim=d["hidden_size"] // nq,
            num_heads=nq,
            num_heads_kv=d["num_key_value_heads"],
            rotary_emb_dim=0,
        ),
        tie_embeddings=d.get("tie_word_embeddings", False),
        norm_eps=d["rms_norm_eps"],
        d_state=d["mamba_d_state"],
        d_conv=d["mamba_d_conv"],
        expand=d["mamba_expand"],
        dt_rank=d["mamba_dt_rank"],
    )


@dataclass(frozen=True)
class MixtralConfig:
    """Sparse-MoE Llama family (Mixtral). Frozen speculator base
    (the reference's EmbedMixtral) and trainable MoE model."""

    src_vocab_size: int = 32000
    emb_dim: int = 4096
    nheads: int = 32
    kvheads: int = 8
    nlayers: int = 32
    hidden_dim: int = 14336
    num_experts: int = 8
    top_k: int = 2
    max_expected_seq_len: int = 4096
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    # training-only knobs (ignored by the dense frozen-base path):
    # per-expert buffer size = capacity_factor * top_k * S / num_experts
    capacity_factor: float = 2.0
    # load-balancing auxiliary loss coefficient (HF router_aux_loss_coef)
    aux_loss_weight: float = 0.02

    @property
    def head_dim(self) -> int:
        return self.emb_dim // self.nheads

    @property
    def n_kv_heads(self) -> int:
        return self.kvheads if self.kvheads else self.nheads

    def n_params(self, include_embeddings: bool = True) -> int:
        d, h, E = self.emb_dim, self.hidden_dim, self.num_experts
        kv_dim = self.n_kv_heads * self.head_dim
        per_layer = (
            d * d  # wq
            + 2 * d * kv_dim  # wk, wv
            + d * d  # wo
            + d * E  # router gate
            + 3 * E * d * h  # per-expert w1, w3, w2
            + 2 * d  # norms
        )
        total = self.nlayers * per_layer + d
        if include_embeddings:
            total += 2 * self.src_vocab_size * d
        return int(total)


@dataclass(frozen=True)
class SarvamConfig:
    """Latent-attention (MLA) MoE family (``model_type: sarvam_mla``;
    models/sarvam.py): every layer attends through a compressed latent
    (``kv_lora_rank`` values and one shared rotary key a position, no
    query compression), the first ``first_k_dense`` layers carry a dense
    SwiGLU MLP, the rest ``num_experts`` sigmoid-routed experts of which
    ``top_k`` serve a token, beside ``num_shared_experts`` that serve
    every token.

    ``experts_held`` = (first id, count) says which of the ``num_experts``
    routed experts this program holds: the router keeps its published
    width, the expert layer computes the part of the result that its own
    experts give, and adds nothing for the others (expert parallelism's
    share of a layer; the exchange is not in this program). ``None`` is
    the whole model. ``src_vocab_size`` is likewise the rows held."""

    src_vocab_size: int = 262144
    emb_dim: int = 4096
    nheads: int = 64
    nlayers: int = 32
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    hidden_dim: int = 16384  # the leading dense layers' MLP
    first_k_dense: int = 1
    moe_hidden_dim: int = 2048  # one expert
    num_experts: int = 128  # the router's width
    experts_held: Optional[Tuple[int, int]] = None
    top_k: int = 8
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    max_expected_seq_len: int = 131072
    rope_theta: float = 10000.0
    rope_factor: float = 40.0
    rope_original_max_position: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    norm_eps: float = 1e-6

    def __post_init__(self):
        first, count = self.held
        if not (0 <= first and count >= 1
                and first + count <= self.num_experts):
            raise ValueError(
                f"experts_held={self.experts_held} is no range of the "
                f"{self.num_experts} routed experts"
            )

    @property
    def held(self) -> Tuple[int, int]:
        """(first id, count) of the routed experts held here."""
        return self.experts_held or (0, self.num_experts)

    @property
    def q_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """What a position leaves in the cache, a layer: the normed
        latent and the rotated shared key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def n_moe_layers(self) -> int:
        return self.nlayers - self.first_k_dense

    def n_params(self) -> int:
        """Parameters held here (the experts and vocabulary rows held)."""
        d, n = self.emb_dim, self.nheads
        attn = (
            d * n * self.q_head_dim
            + d * self.latent_dim
            + self.kv_lora_rank
            + self.kv_lora_rank * n * (self.qk_nope_head_dim + self.v_head_dim)
            + n * self.v_head_dim * d
            + 2 * d
        )
        dense = 3 * d * self.hidden_dim
        moe = (
            d * self.num_experts + self.num_experts
            + 3 * d * self.moe_hidden_dim
            * (self.held[1] + self.num_shared_experts)
        )
        return int(
            self.nlayers * attn
            + self.first_k_dense * dense
            + self.n_moe_layers * moe
            + d
            + 2 * self.src_vocab_size * d
        )


def sarvam_config(d: dict) -> SarvamConfig:
    """A published ``config.json`` of ``model_type: sarvam_mla`` as the
    family's config. A file that states a chip's share of a deployment
    gives the experts held as ``num_experts`` with the router's width
    under ``published`` and the first held id as ``first_expert_held``
    (benchmark/configs/sarvam-105b.1chip.json); without ``published`` the
    model is whole. The config has no key for the scoring rule, the
    routing groups or where ``use_qk_norm`` acts: models/sarvam.py says
    how each is read."""
    if d.get("q_lora_rank"):
        raise ValueError(
            "sarvam_mla with q_lora_rank: the family's queries are not "
            "compressed (models/sarvam.py)"
        )
    rs = d.get("rope_scaling") or {}
    if rs.get("type", "deepseek_yarn") != "deepseek_yarn":
        raise ValueError(f"rope_scaling type {rs.get('type')!r}: the family "
                         "has the deepseek_yarn frequencies")
    held = d["num_experts"]
    published = (d.get("published") or {}).get("num_experts", held)
    return SarvamConfig(
        src_vocab_size=d["vocab_size"],
        emb_dim=d["hidden_size"],
        nheads=d["num_attention_heads"],
        nlayers=d["num_hidden_layers"],
        qk_nope_head_dim=d["qk_nope_head_dim"],
        qk_rope_head_dim=d["qk_rope_head_dim"],
        v_head_dim=d["v_head_dim"],
        kv_lora_rank=d["kv_lora_rank"],
        hidden_dim=d["intermediate_size"],
        first_k_dense=d.get("first_k_dense_replace", 0),
        moe_hidden_dim=d["moe_intermediate_size"],
        num_experts=published,
        experts_held=(
            (int(d.get("first_expert_held", 0)), held)
            if held != published else None
        ),
        top_k=d["num_experts_per_tok"],
        num_shared_experts=d.get("num_shared_experts", 0),
        routed_scaling_factor=d.get("routed_scaling_factor", 1.0),
        max_expected_seq_len=d["max_position_embeddings"],
        rope_theta=d.get("rope_theta", 10000.0),
        rope_factor=rs.get("factor", 1.0),
        rope_original_max_position=rs.get(
            "original_max_position_embeddings", d["max_position_embeddings"]
        ),
        rope_beta_fast=rs.get("beta_fast", 32.0),
        rope_beta_slow=rs.get("beta_slow", 1.0),
        rope_mscale=rs.get("mscale", 1.0),
        rope_mscale_all_dim=rs.get("mscale_all_dim", 0.0),
        norm_eps=d["rms_norm_eps"],
    )


# the kinds of layer of the window-and-full family: (attention, feed-forward)
KEXAONE_ATTN_KINDS = ("sliding_attention", "full_attention")
KEXAONE_MLP_KINDS = ("dense", "sparse")


def _kexaone_kind(attn: str, mlp: str) -> str:
    """(attention, feed-forward) -> the name of the kind's stack in the
    tree: ``sliding_dense``, ``sliding_sparse``, ``full_dense`` or
    ``full_sparse``."""
    return attn.split("_")[0] + "_" + mlp


KEXAONE_LAYER_KINDS = tuple(
    _kexaone_kind(a, m)
    for a in KEXAONE_ATTN_KINDS for m in KEXAONE_MLP_KINDS
)


@dataclass(frozen=True)
class KExaoneConfig:
    """Window-and-full-attention MoE family (``model_type: exaone_moe``;
    models/kexaone.py): grouped-query attention whose layers are of two
    kinds, ``layer_types[i]`` = ``"sliding_attention"`` (a position sees
    itself and the ``sliding_window - 1`` before it; rotary embedding) or
    ``"full_attention"`` (every earlier position; no positional
    embedding), and whose feed-forward is of two kinds,
    ``mlp_layer_types[i]`` = ``"dense"`` (a SwiGLU of ``hidden_dim``) or
    ``"sparse"`` (``num_experts`` sigmoid-routed experts of
    ``moe_hidden_dim``, ``top_k`` a token, beside ``num_shared_experts``
    that serve every token). Weights of one shape whatever the attention
    kind.

    ``experts_held`` and ``src_vocab_size``: as ``SarvamConfig`` has them
    (the routed experts and the vocabulary rows this program holds)."""

    src_vocab_size: int = 153600
    emb_dim: int = 6144
    nheads: int = 64
    kvheads: int = 8
    head_dim: int = 128
    nlayers: int = 48
    layer_types: Tuple[str, ...] = ()
    mlp_layer_types: Tuple[str, ...] = ()
    sliding_window: int = 128
    hidden_dim: int = 18432  # a dense layer's MLP
    moe_hidden_dim: int = 2048  # one expert
    num_experts: int = 128  # the router's width
    experts_held: Optional[Tuple[int, int]] = None
    top_k: int = 8
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    max_expected_seq_len: int = 262144
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-5

    def __post_init__(self):
        first, count = self.held
        if not (0 <= first and count >= 1
                and first + count <= self.num_experts):
            raise ValueError(
                f"experts_held={self.experts_held} is no range of the "
                f"{self.num_experts} routed experts"
            )
        for name, kinds in (("layer_types", KEXAONE_ATTN_KINDS),
                            ("mlp_layer_types", KEXAONE_MLP_KINDS)):
            got = getattr(self, name)
            if len(got) != self.nlayers or set(got) - set(kinds):
                raise ValueError(
                    f"{name} must name one of {kinds} for each of the "
                    f"{self.nlayers} layers, got {got}"
                )
        if self.nheads % self.kvheads:
            raise ValueError(
                f"{self.nheads} query heads do not share {self.kvheads} "
                "kv heads evenly"
            )

    @property
    def held(self) -> Tuple[int, int]:
        """(first id, count) of the routed experts held here."""
        return self.experts_held or (0, self.num_experts)

    def kind(self, i: int) -> str:
        """Layer ``i``'s kind, the name of its stack in the parameter
        tree."""
        return _kexaone_kind(self.layer_types[i], self.mlp_layer_types[i])

    @property
    def stacks(self):
        """``{kind: the indices of its layers}``, kinds in the order they
        first occur. A layer's index in its stack is its place among the
        layers of its kind."""
        out = {}
        for i in range(self.nlayers):
            out.setdefault(self.kind(i), []).append(i)
        return out

    @property
    def window_layers(self) -> Tuple[int, ...]:
        return tuple(
            i for i, t in enumerate(self.layer_types)
            if t == "sliding_attention"
        )

    @property
    def full_layers(self) -> Tuple[int, ...]:
        return tuple(
            i for i, t in enumerate(self.layer_types) if t == "full_attention"
        )

    @property
    def n_moe_layers(self) -> int:
        return sum(t == "sparse" for t in self.mlp_layer_types)

    def n_params(self) -> int:
        """Parameters held here (the experts and vocabulary rows held)."""
        d, hd = self.emb_dim, self.head_dim
        attn = (
            2 * d * self.nheads * hd + 2 * d * self.kvheads * hd
            + 2 * hd + 2 * d
        )
        dense = 3 * d * self.hidden_dim
        moe = (
            d * self.num_experts + self.num_experts
            + 3 * d * self.moe_hidden_dim
            * (self.held[1] + self.num_shared_experts)
        )
        n_moe = self.n_moe_layers
        return int(
            self.nlayers * attn
            + (self.nlayers - n_moe) * dense
            + n_moe * moe
            + d
            + 2 * self.src_vocab_size * d
        )


def kexaone_config(d: dict) -> KExaoneConfig:
    """A published ``config.json`` of ``model_type: exaone_moe`` as the
    family's config. A file that states a chip's share of a deployment
    gives the experts held as ``num_experts`` with the router's width
    under ``published`` and the first held id as ``first_expert_held``
    (benchmark/configs/k-exaone-236b.1chip.json), as ``sarvam_config``
    reads them. The multi-token-prediction module
    (``num_nextn_predict_layers``) is not built: it does not enter the
    next-token logits, and a config that asks for it is refused by name.
    models/kexaone.py says how the keys the config does not have are
    read."""
    if d.get("num_nextn_predict_layers"):
        raise ValueError(
            "exaone_moe with num_nextn_predict_layers="
            f"{d['num_nextn_predict_layers']}: the multi-token-prediction "
            "module is not built (its block is not fixed by the config); "
            "set it to 0 to serve the trunk's next-token logits"
        )
    if d.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError(
            f"scoring_func {d['scoring_func']!r}: the family's router "
            "scores by sigmoid"
        )
    if d.get("n_group", 1) != 1 or d.get("topk_group", 1) != 1:
        raise ValueError(
            "exaone_moe with routing groups (n_group, topk_group != 1): "
            "the router chooses over all experts"
        )
    if not d.get("norm_topk_prob", True):
        raise ValueError(
            "norm_topk_prob false: the family's chosen weights are normalised"
        )
    rp = d.get("rope_parameters") or {}
    if rp.get("rope_type", "default") != "default":
        raise ValueError(
            f"rope_type {rp.get('rope_type')!r}: the family has the "
            "default frequencies"
        )
    L = d["num_hidden_layers"]
    layer_types = tuple(d["layer_types"])
    first_dense = d.get("first_k_dense_replace", 0)
    mlp_types = tuple(d.get("mlp_layer_types") or (
        "dense" if i < first_dense else "sparse" for i in range(L)
    ))
    held = d["num_experts"]
    published = (d.get("published") or {}).get("num_experts", held)
    return KExaoneConfig(
        src_vocab_size=d["vocab_size"],
        emb_dim=d["hidden_size"],
        nheads=d["num_attention_heads"],
        kvheads=d["num_key_value_heads"],
        head_dim=d["head_dim"],
        nlayers=L,
        layer_types=layer_types,
        mlp_layer_types=mlp_types,
        sliding_window=d["sliding_window"],
        hidden_dim=d["intermediate_size"],
        moe_hidden_dim=d["moe_intermediate_size"],
        num_experts=published,
        experts_held=(
            (int(d.get("first_expert_held", 0)), held)
            if held != published else None
        ),
        top_k=d["num_experts_per_tok"],
        num_shared_experts=d.get("num_shared_experts", 0),
        routed_scaling_factor=d.get("routed_scaling_factor", 1.0),
        max_expected_seq_len=d["max_position_embeddings"],
        rope_theta=float(rp.get("rope_theta", d.get("rope_theta", 10000.0))),
        norm_eps=d["rms_norm_eps"],
    )


# the kinds of layer of the sparse-and-linear family, by ``mixer_types``'
# names: the name of the kind's stack in the parameter tree
SALA_MIXER_KINDS = {"minicpm4": "sparse", "lightning-attn": "lightning"}


@dataclass(frozen=True)
class SalaSparseConfig:
    """The sizes of a ``minicpm4`` layer's block choice (InfLLM-V2;
    MiniCPM4's published ``sparse_config``): keys are mean-pooled over
    ``kernel_size`` positions every ``kernel_stride``; a query scores
    those, the scores pool to blocks of ``block_size`` positions, and
    ``topk`` blocks are attended, among them always the first
    ``init_blocks`` and the ``window_size`` positions' worth that end at
    the query's own. A query with ``t + 1 <= dense_len`` attends
    everything before it."""

    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    topk: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    dense_len: int = 8192

    def __post_init__(self):
        if self.kernel_size != 2 * self.kernel_stride:
            raise ValueError(
                f"sparse_config kernel_size={self.kernel_size} with "
                f"kernel_stride={self.kernel_stride}: the block pooling is "
                "built for windows that overlap by half (kernel = 2 x stride)"
            )
        for name in ("block_size", "window_size", "dense_len"):
            whole = self.kernel_stride if name == "block_size" else self.block_size
            if getattr(self, name) % whole:
                raise ValueError(
                    f"sparse_config {name}={getattr(self, name)} is no "
                    f"multiple of {whole}"
                )
        if self.topk < self.init_blocks + self.window_blocks:
            raise ValueError(
                f"sparse_config topk={self.topk} cannot hold the "
                f"{self.init_blocks} initial and {self.window_blocks} "
                "window blocks that are always chosen"
            )

    @property
    def per_block(self) -> int:
        """Compressed keys that start inside one block."""
        return self.block_size // self.kernel_stride

    @property
    def window_blocks(self) -> int:
        return self.window_size // self.block_size

    @property
    def dense_blocks(self) -> int:
        """Blocks a position that attends everything can see at most."""
        return self.dense_len // self.block_size

    @property
    def list_blocks(self) -> int:
        """The longest list of blocks a position attends."""
        return max(self.topk, self.dense_blocks)


@dataclass(frozen=True)
class SalaConfig:
    """Sparse-and-linear-attention family (``model_type: minicpm_sala``;
    models/minicpm_sala.py): layer ``i`` is of kind ``mixer_types[i]``,
    ``"minicpm4"`` (grouped-query attention over the blocks of its
    context that a query chooses through compressed keys, ``sparse``) or
    ``"lightning-attn"`` (linear attention with a decay a head, a
    ``head_dim`` x ``head_dim`` state a head and stream), each before a
    SwiGLU of ``hidden_dim``. The family scales its embedding
    (``scale_emb``), what a sub-block adds to the residual
    (``scale_depth / sqrt(depth_published)``) and its logits (``1 /
    (emb_dim / dim_model_base)``)."""

    src_vocab_size: int = 73448
    emb_dim: int = 4096
    nheads: int = 32
    kvheads: int = 2
    head_dim: int = 128
    nlayers: int = 32
    mixer_types: Tuple[str, ...] = ()
    hidden_dim: int = 16384
    lightning_nh: int = 32
    lightning_head_dim: int = 128
    sparse: SalaSparseConfig = SalaSparseConfig()
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    depth_published: int = 32
    max_expected_seq_len: int = 524288
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6

    def __post_init__(self):
        got = self.mixer_types
        if len(got) != self.nlayers or set(got) - set(SALA_MIXER_KINDS):
            raise ValueError(
                f"mixer_types must name one of {tuple(SALA_MIXER_KINDS)} "
                f"for each of the {self.nlayers} layers, got {got}"
            )
        if self.nheads % self.kvheads:
            raise ValueError(
                f"{self.nheads} query heads do not share {self.kvheads} "
                "kv heads evenly"
            )

    def kind(self, i: int) -> str:
        """Layer ``i``'s kind, the name of its stack in the tree."""
        return SALA_MIXER_KINDS[self.mixer_types[i]]

    @property
    def stacks(self):
        """``{kind: the indices of its layers}``, kinds in the order they
        first occur."""
        out = {}
        for i in range(self.nlayers):
            out.setdefault(self.kind(i), []).append(i)
        return out

    @property
    def sparse_layers(self) -> Tuple[int, ...]:
        return tuple(self.stacks.get("sparse", ()))

    @property
    def lightning_layers(self) -> Tuple[int, ...]:
        return tuple(self.stacks.get("lightning", ()))

    @property
    def residual_gain(self) -> float:
        return self.scale_depth / self.depth_published**0.5

    @property
    def logit_divisor(self) -> float:
        return self.emb_dim / self.dim_model_base

    def layer_params(self, kind: str) -> int:
        d, f = self.emb_dim, self.hidden_dim
        if kind == "sparse":
            heads, kv, hd = self.nheads * self.head_dim, (
                self.kvheads * self.head_dim), self.head_dim
            norms = 2 * hd + 2 * d
        else:
            heads = kv = self.lightning_nh * self.lightning_head_dim
            norms = 2 * self.lightning_head_dim + heads + 2 * d
        return 3 * d * heads + 2 * d * kv + 3 * d * f + norms

    def n_params(self) -> int:
        return int(
            sum(self.layer_params(self.kind(i)) for i in range(self.nlayers))
            + self.emb_dim
            + 2 * self.src_vocab_size * self.emb_dim
        )


# what the family's code has and a ``config.json`` could switch off or
# on: a config that asks otherwise is refused by the key's name
_SALA_FIXED = {
    "attention_bias": False,
    "attn_use_rope": False,
    "qk_norm": True,
    "lightning_use_rope": True,
    "use_output_gate": True,
    "use_output_norm": True,
    "attn_use_output_gate": True,
    "tie_word_embeddings": False,
    "hidden_act": "silu",
    "lightning_scale": "1/sqrt(d)",
}


def minicpm_sala_config(d: dict) -> SalaConfig:
    """A published ``config.json`` of ``model_type: minicpm_sala`` as the
    family's config. A file that keeps a slice of the stack states the
    layers kept as ``num_hidden_layers`` and ``mixer_types`` and the
    published depth under ``published`` (the residual gain keeps the
    published depth; benchmark/configs/minicpm-sala-9b.1chip.json). The
    sizes of the block choice come from ``sparse_config`` (MiniCPM4's
    published key; the family's defaults where the file has none).
    models/minicpm_sala.py says how the keys the config does not have
    are read; a key that asks for what is not built is refused by
    name."""
    for key, built in _SALA_FIXED.items():
        if key in d and d[key] != built:
            raise ValueError(
                f"minicpm_sala with {key}={d[key]!r}: only {key}={built!r} "
                "is built"
            )
    if d.get("lightning_nkv", d["lightning_nh"]) != d["lightning_nh"]:
        raise ValueError(
            f"lightning_nkv={d['lightning_nkv']} != lightning_nh="
            f"{d['lightning_nh']}: the lightning layers are built with a "
            "key and value head for every query head"
        )
    L = d["num_hidden_layers"]
    published = (d.get("published") or {}).get("num_hidden_layers", L)
    if d.get("mup_denominator", published) != published:
        raise ValueError(
            f"mup_denominator={d['mup_denominator']} is not the published "
            f"depth {published}: the residual gain is scale_depth / "
            "sqrt(published depth)"
        )
    return SalaConfig(
        src_vocab_size=d["vocab_size"],
        emb_dim=d["hidden_size"],
        nheads=d["num_attention_heads"],
        kvheads=d["num_key_value_heads"],
        head_dim=d["head_dim"],
        nlayers=L,
        mixer_types=tuple(d["mixer_types"]),
        hidden_dim=d["intermediate_size"],
        lightning_nh=d["lightning_nh"],
        lightning_head_dim=d["lightning_head_dim"],
        sparse=SalaSparseConfig(**(d.get("sparse_config") or {})),
        scale_emb=float(d["scale_emb"]),
        scale_depth=float(d["scale_depth"]),
        dim_model_base=d["dim_model_base"],
        depth_published=published,
        max_expected_seq_len=d["max_position_embeddings"],
        rope_theta=float(d.get("rope_theta", 10000.0)),
        norm_eps=d["rms_norm_eps"],
    )


# the kinds of operator of the short-convolution family, by
# ``layer_types``' names
LFM2_OPERATOR_KINDS = ("conv", "full_attention")


@dataclass(frozen=True)
class Lfm2MoeConfig:
    """Short-convolution-and-attention MoE family (``model_type:
    lfm2_moe``; models/lfm2.py): layer ``i``'s operator is
    ``layer_types[i]`` = ``"conv"`` (a gated depthwise causal convolution
    of ``conv_kernel`` positions: a stream keeps ``conv_kernel - 1``
    positions of ``B * x``, whatever its context) or ``"full_attention"``
    (grouped-query attention, heads of ``emb_dim / nheads``, QK-norm by
    head then rotary over the whole head); its feed-forward is a dense
    SwiGLU of ``hidden_dim`` in the first ``num_dense_layers`` layers and
    ``num_experts`` sigmoid-routed experts of ``moe_hidden_dim``, ``top_k``
    a token and no shared one, after them. The head is the embedding.

    ``experts_held``: as ``SarvamConfig`` has it (the routed experts this
    program holds; all of them when None). ``router_sum_eps`` stands
    under the sum that normalises the chosen scores."""

    src_vocab_size: int = 65536
    emb_dim: int = 2048
    nheads: int = 32
    kvheads: int = 8
    nlayers: int = 40
    layer_types: Tuple[str, ...] = ()
    conv_kernel: int = 3  # conv_L_cache
    num_dense_layers: int = 2
    hidden_dim: int = 11776  # a dense layer's MLP
    moe_hidden_dim: int = 1536  # one expert
    num_experts: int = 64  # the router's width
    experts_held: Optional[Tuple[int, int]] = None
    top_k: int = 4
    routed_scaling_factor: float = 1.0
    router_sum_eps: float = 1e-6
    max_expected_seq_len: int = 128000
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-5

    def __post_init__(self):
        first, count = self.held
        if not (0 <= first and count >= 1
                and first + count <= self.num_experts):
            raise ValueError(
                f"experts_held={self.experts_held} is no range of the "
                f"{self.num_experts} routed experts"
            )
        got = self.layer_types
        if len(got) != self.nlayers or set(got) - set(LFM2_OPERATOR_KINDS):
            raise ValueError(
                f"layer_types must name one of {LFM2_OPERATOR_KINDS} for "
                f"each of the {self.nlayers} layers, got {got}"
            )
        if self.nheads % self.kvheads or self.emb_dim % self.nheads:
            raise ValueError(
                f"{self.nheads} query heads over {self.kvheads} kv heads "
                f"of a hidden size of {self.emb_dim} do not divide evenly"
            )
        if self.conv_kernel < 2:
            raise ValueError(
                f"conv_L_cache={self.conv_kernel}: a convolution of one "
                "position keeps no window"
            )

    @property
    def head_dim(self) -> int:
        return self.emb_dim // self.nheads

    @property
    def held(self) -> Tuple[int, int]:
        """(first id, count) of the routed experts held here."""
        return self.experts_held or (0, self.num_experts)

    @property
    def conv_layers(self) -> Tuple[int, ...]:
        return tuple(
            i for i, t in enumerate(self.layer_types) if t == "conv"
        )

    @property
    def attn_layers(self) -> Tuple[int, ...]:
        return tuple(
            i for i, t in enumerate(self.layer_types)
            if t == "full_attention"
        )

    def sparse(self, i: int) -> bool:
        """Layer ``i``'s feed-forward is the expert layer."""
        return i >= self.num_dense_layers

    @property
    def n_moe_layers(self) -> int:
        return max(0, self.nlayers - self.num_dense_layers)

    def n_params(self) -> int:
        """Parameters held here (the head is the embedding's)."""
        d, hd = self.emb_dim, self.head_dim
        conv = 3 * d * d + d * d + d * self.conv_kernel
        attn = 2 * d * self.nheads * hd + 2 * d * self.kvheads * hd + 2 * hd
        dense = 3 * d * self.hidden_dim
        moe = (
            d * self.num_experts + self.num_experts
            + 3 * d * self.moe_hidden_dim * self.held[1]
        )
        n_moe = self.n_moe_layers
        return int(
            len(self.conv_layers) * conv
            + len(self.attn_layers) * attn
            + (self.nlayers - n_moe) * dense
            + n_moe * moe
            + 2 * d * self.nlayers
            + d
            + self.src_vocab_size * d
        )


def lfm2_moe_config(d: dict) -> Lfm2MoeConfig:
    """A published ``config.json`` of ``model_type: lfm2_moe`` as the
    family's config. A file that keeps a slice of the stack states the
    layers kept as ``num_hidden_layers`` and ``layer_types``
    (benchmark/configs/lfm2-24b-a2b.1chip.json); one that states a chip's
    share of the experts gives it as ``sarvam_config`` reads it.
    models/lfm2.py says how the keys the config does not have are read; a
    key that asks for what is not built is refused by name."""
    if d.get("conv_bias", False):
        raise ValueError(
            "lfm2_moe with conv_bias=True: the short convolution is built "
            "without a bias (conv_bias false, as published)"
        )
    if not d.get("norm_topk_prob", True):
        raise ValueError(
            "norm_topk_prob false: the family's chosen weights are normalised"
        )
    if not d.get("use_expert_bias", True):
        raise ValueError(
            "use_expert_bias false: the family's router chooses by a "
            "biased score (a zero bias is the same router)"
        )
    if not d.get("tie_word_embeddings", True):
        raise ValueError(
            "tie_word_embeddings false: the family's head is its embedding"
        )
    rp = d.get("rope_parameters") or {}
    if rp.get("rope_type", "default") != "default":
        raise ValueError(
            f"rope_type {rp.get('rope_type')!r}: the family has the "
            "default frequencies"
        )
    held = d["num_experts"]
    published = (d.get("published") or {}).get("num_experts", held)
    return Lfm2MoeConfig(
        src_vocab_size=d["vocab_size"],
        emb_dim=d["hidden_size"],
        nheads=d["num_attention_heads"],
        kvheads=d["num_key_value_heads"],
        nlayers=d["num_hidden_layers"],
        layer_types=tuple(d["layer_types"]),  # another name: refused there
        conv_kernel=d["conv_L_cache"],
        num_dense_layers=d["num_dense_layers"],
        hidden_dim=d["intermediate_size"],
        moe_hidden_dim=d["moe_intermediate_size"],
        num_experts=published,
        experts_held=(
            (int(d.get("first_expert_held", 0)), held)
            if held != published else None
        ),
        top_k=d["num_experts_per_tok"],
        routed_scaling_factor=float(d.get("routed_scaling_factor", 1.0)),
        max_expected_seq_len=d["max_position_embeddings"],
        rope_theta=float(rp.get("rope_theta", d.get("rope_theta", 1e6))),
        norm_eps=d["norm_eps"],
    )


PHI4FLASH_LAYER_KINDS = ("mamba", "window", "full", "gmu", "cross")


@dataclass(frozen=True)
class Phi4FlashConfig:
    """The Phi-4-mini-flash family (``model_type: phi4flash``;
    models/phi4flash.py): a self-decoder of Mamba-1 mixers and
    window-attention layers, one full-attention layer whose keys and
    values are the only cache of the layers behind it, and a
    cross-decoder of gated memory units (which gate the scan output of
    the self-decoder's last Mamba layer) and cross-attention layers (a
    query and an output projection alone, over the full layer's keys and
    values). Every attention is differential: heads in pairs, two
    softmaxes, their difference under a learned weight. LayerNorm with
    weight and bias, a SwiGLU MLP of ``hidden_dim`` a layer, the head the
    embedding, no positional embedding.

    ``kind(i)`` is the layer rule: with ``half = nlayers / 2``, even
    layers are ``mamba`` up to ``half`` (layer ``half`` hands its scan
    output out) and ``gmu`` past it; odd layers are ``window`` below
    ``half``, ``full`` at ``half + 1`` and ``cross`` past it. The Mamba
    sizes are the family's defaults (``d_state`` 16, ``d_conv`` 4,
    ``expand`` 2, ``dt_rank`` ceil(emb_dim / 16)): the published config
    has no key for them."""

    src_vocab_size: int = 200064
    emb_dim: int = 2560
    nheads: int = 40
    kvheads: int = 20
    nlayers: int = 32
    hidden_dim: int = 10240
    mb_per_layer: int = 2
    sliding_window: int = 512
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    max_expected_seq_len: int = 262144
    norm_eps: float = 1e-5  # layer_norm_eps
    subln_eps: float = 1e-5  # the norm by head of a differential output

    def __post_init__(self):
        if self.mb_per_layer != 2:
            raise ValueError(
                f"mb_per_layer={self.mb_per_layer}: the stack is built for "
                "Mamba (or gated memory) layers alternating with attention "
                "layers, mb_per_layer 2"
            )
        if self.nlayers % 4 or self.nlayers < 8:
            raise ValueError(
                f"num_hidden_layers={self.nlayers}: the layer rule needs a "
                "multiple of 4, at least 8 (layer n/2 a Mamba layer that "
                "hands its scan output out, n/2 + 1 the full layer, a gated "
                "memory unit and a cross layer behind them)"
            )
        if (self.nheads % 2 or self.kvheads % 2
                or self.nheads % self.kvheads or self.emb_dim % self.nheads):
            raise ValueError(
                f"{self.nheads} query heads over {self.kvheads} kv heads of "
                f"a hidden size of {self.emb_dim}: differential attention "
                "pairs neighbouring heads of both and the pairs divide evenly"
            )
        if self.sliding_window < 1:
            raise ValueError(
                f"sliding_window={self.sliding_window}: a window layer sees "
                "at least its own position"
            )

    @property
    def head_dim(self) -> int:
        return self.emb_dim // self.nheads

    @property
    def d_inner(self) -> int:
        return self.expand * self.emb_dim

    @property
    def dt_rank_(self) -> int:
        return -(-self.emb_dim // 16)

    @property
    def hand_out_layer(self) -> int:
        """The Mamba layer whose scan output the gated memory units read."""
        return self.nlayers // 2

    @property
    def full_layer(self) -> int:
        """The one layer whose keys and values are kept a position."""
        return self.nlayers // 2 + 1

    def kind(self, i: int) -> str:
        half = self.nlayers // 2
        if i % self.mb_per_layer == 0:
            return "mamba" if i <= half else "gmu"
        if i < half:
            return "window"
        return "full" if i == half + 1 else "cross"

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i in range(self.nlayers) if self.kind(i) == kind)

    @staticmethod
    def lambda_init(i: int) -> float:
        """The constant part of a differential layer's weight, by the
        layer's index."""
        return 0.8 - 0.6 * math.exp(-0.3 * i)

    def n_params(self) -> int:
        """Parameters of the whole model (the head is the embedding's)."""
        d, hd, di = self.emb_dim, self.head_dim, self.d_inner
        N, R = self.d_state, self.dt_rank_
        mamba = (
            2 * d * di + di * (self.d_conv + 1) + di * (R + 2 * N)
            + R * di + di + di * N + di + di * d
        )
        cross = 2 * (d * self.nheads * hd) + self.nheads * hd + d + 6 * hd
        attn = cross + 2 * (d * self.kvheads * hd + self.kvheads * hd)
        per = {
            "mamba": mamba, "window": attn, "full": attn, "cross": cross,
            "gmu": 2 * d * di,
        }
        return int(
            sum(per[self.kind(i)] for i in range(self.nlayers))
            + self.nlayers * (3 * d * self.hidden_dim + 4 * d)
            + 2 * d
            + self.src_vocab_size * d
        )


# what a published phi4flash config.json says and the stack takes as it
# is: the value the family is built for, by key
_PHI4FLASH_FIXED = {
    "hidden_act": "silu", "mlp_bias": False, "lm_head_bias": False,
    "tie_word_embeddings": True, "embd_pdrop": 0, "resid_pdrop": 0,
    "model_type": "phi4flash",
}
# keys of a configuration's file that say nothing of the model's shape
_PHI4FLASH_NOTES = (
    "family", "source", "published", "reduced", "assumed", "deployment",
    "n_params",
    "weight_bytes_bfloat16",
)


def phi4flash_config(d: dict) -> Phi4FlashConfig:
    """A published ``config.json`` of ``model_type: phi4flash`` as the
    family's config. models/phi4flash.py says how what the config has no
    key for is read; a key this mapping does not cover, or a value the
    stack is not built for, is refused by name."""
    d = dict(d)
    for key in _PHI4FLASH_NOTES:
        d.pop(key, None)
    for key, built in _PHI4FLASH_FIXED.items():
        got = d.pop(key, built)
        if got != built:
            raise ValueError(
                f"phi4flash with {key}={got!r}: the family is built for "
                f"{key}={built!r}"
            )
    try:
        cfg = Phi4FlashConfig(
            src_vocab_size=d.pop("vocab_size"),
            emb_dim=d.pop("hidden_size"),
            nheads=d.pop("num_attention_heads"),
            kvheads=d.pop("num_key_value_heads"),
            nlayers=d.pop("num_hidden_layers"),
            hidden_dim=d.pop("intermediate_size"),
            mb_per_layer=d.pop("mb_per_layer"),
            sliding_window=d.pop("sliding_window"),
            max_expected_seq_len=d.pop("max_position_embeddings"),
            norm_eps=d.pop("layer_norm_eps"),
        )
    except KeyError as e:
        raise ValueError(
            f"a phi4flash config.json has to give {e.args[0]!r}"
        ) from None
    if d:
        raise ValueError(
            f"phi4flash config keys {sorted(d)} are not covered: the family "
            "reads hidden_size, intermediate_size, layer_norm_eps, "
            "max_position_embeddings, mb_per_layer, num_attention_heads, "
            "num_hidden_layers, num_key_value_heads, sliding_window and "
            f"vocab_size, and takes {sorted(_PHI4FLASH_FIXED)} at the "
            "values it is built for"
        )
    return cfg
