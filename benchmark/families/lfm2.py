"""LFM2-MoE ``config.json`` keys (``model_type: lfm2_moe``) -> the
program's ``Lfm2MoeConfig``. The program keeps that mapping itself, for
``serve/replica.py``'s ``model_cfg.json``: the layers kept
(``num_hidden_layers`` and ``layer_types`` of ``published``'s) are read
from the configuration's file as the guide's section 4 has it written;
every expert and the whole vocabulary are held."""


def model_config(c):
    from fms_fsdp_tpu.models.configs import lfm2_moe_config

    return lfm2_moe_config(c)
