"""Jamba ``config.json`` keys -> the program's hybrid ``MambaConfig``
(Mamba-1 mixers, attention with no rotary embedding, a dense MLP after
every mixer, the head tied to the embedding). The program keeps that
mapping itself, for ``serve/replica.py``'s ``model_cfg.json``; which
layers it makes attention is held against
``reference/jamba.py::layer_kind`` by the driver's comparison of the two
parameter trees."""


def model_config(c):
    from fms_fsdp_tpu.models.configs import jamba_config

    return jamba_config(c)
