"""Configuration files (sizes as run) and the builder modules they name."""
