"""Cell kinds: one general generator per kind of configuration."""
