"""Traffic generators, one per kind, and the mixes they read."""
