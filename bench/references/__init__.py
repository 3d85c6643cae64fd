"""Plain references, one module per semantics, named by a configuration's
``reference`` key."""
