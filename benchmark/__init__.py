"""The benchmark: cells of (model configuration x traffic mix) driven through
``RolloutEngine`` and ``train_step`` on the chip. See ``README.md``."""
