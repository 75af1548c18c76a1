"""Model configurations as run, each beside its plain reference."""
