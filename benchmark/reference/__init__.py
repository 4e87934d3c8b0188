"""The plain reference of each configuration."""
