"""One module per model family: a configuration file's sizes as the
program's ``ModelConfig``."""
