"""Plain float32 references, one per model family, found by the name
in a configuration's "reference" key."""
