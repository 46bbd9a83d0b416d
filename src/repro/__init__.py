"""SPB/Jigsaw reproduction framework (see README.md for the module map)."""
