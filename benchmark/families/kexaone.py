"""K-EXAONE ``config.json`` keys (``model_type: exaone_moe``) -> the
program's ``KExaoneConfig``. The program keeps that mapping itself, for
``serve/replica.py``'s ``model_cfg.json``: the experts held
(``num_experts`` of ``published.num_experts``, from ``first_expert_held``
on), the vocabulary rows held and the layers kept (``layer_types``,
``mlp_layer_types``) are read from the configuration's file as the
guide's section 4 has it written."""


def model_config(c):
    from fms_fsdp_tpu.models.configs import kexaone_config

    return kexaone_config(c)
