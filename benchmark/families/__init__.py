"""One file per model family: how a configuration file (the published
``config.json`` keys) becomes the program's own model config. The only
place, with ``drivers/``, where the benchmark touches the program."""
