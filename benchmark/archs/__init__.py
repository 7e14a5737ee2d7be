"""One module per ``model_type`` of a configuration file: the published keys
-> the program's ``ModelConfig``. ``manifest.model_config`` finds the module
by that name, so an architecture with keys of its own (rope scaling, sliding
window, experts) arrives as a file."""
