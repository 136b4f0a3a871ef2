"""One module per architecture, named by a configuration file's
``model_type`` and found by ``spec.architecture``."""
