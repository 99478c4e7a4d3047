"""MiniCPM-SALA ``config.json`` keys (``model_type: minicpm_sala``) -> the
program's ``SalaConfig``. The program keeps that mapping itself, for
``serve/replica.py``'s ``model_cfg.json``: the layers kept
(``num_hidden_layers`` and ``mixer_types`` of ``published``'s), the
published depth that the residual gain keeps, and the sizes of the block
choice (``sparse_config``, under ``assumed`` in the configuration's file)
are read from the configuration's file as the guide's section 4 has it
written."""


def model_config(c):
    from fms_fsdp_tpu.models.configs import minicpm_sala_config

    return minicpm_sala_config(c)
