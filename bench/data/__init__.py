"""Data generators, one module per data shape, named by a config's
``data`` key."""
