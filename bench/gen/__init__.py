"""Data generators, one module per schema, found by the configuration's
``generator`` key."""
