"""Sarvam ``config.json`` keys (``model_type: sarvam_mla``) -> the
program's ``SarvamConfig``. The program keeps that mapping itself, for
``serve/replica.py``'s ``model_cfg.json``: the experts held
(``num_experts`` of ``published.num_experts``, from ``first_expert_held``
on) and the vocabulary rows held are read from the configuration's
file as the guide's section 4 has it written."""


def model_config(c):
    from fms_fsdp_tpu.models.configs import sarvam_config

    return sarvam_config(c)
