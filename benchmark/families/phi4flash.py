"""Phi-4-mini-flash ``config.json`` keys (``model_type: phi4flash``) ->
the program's ``Phi4FlashConfig``. The program keeps that mapping itself,
for ``serve/replica.py``'s ``model_cfg.json``; the whole model is held
(every layer, every width, the whole vocabulary), so nothing of the file
is cut; which layer is of which kind is held against
``reference/phi4flash.py::layer_kind`` by the driver's comparison of the
two parameter trees."""


def model_config(c):
    from fms_fsdp_tpu.models.configs import phi4flash_config

    return phi4flash_config(c)
