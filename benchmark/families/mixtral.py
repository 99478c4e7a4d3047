"""Mixtral ``config.json`` keys -> the program's ``MixtralConfig``."""


def model_config(c):
    from fms_fsdp_tpu.models.configs import MixtralConfig

    return MixtralConfig(
        src_vocab_size=c["vocab_size"],
        emb_dim=c["hidden_size"],
        nheads=c["num_attention_heads"],
        kvheads=c["num_key_value_heads"],
        nlayers=c["num_hidden_layers"],
        hidden_dim=c["intermediate_size"],
        num_experts=c["num_local_experts"],
        top_k=c["num_experts_per_tok"],
        max_expected_seq_len=c["max_position_embeddings"],
        rope_theta=c["rope_theta"],
        norm_eps=c["rms_norm_eps"],
        aux_loss_weight=c["router_aux_loss_coef"],
    )
