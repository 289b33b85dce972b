"""Plain float32 references, one module per family of configurations,
named by a configuration file's ``reference`` key."""
