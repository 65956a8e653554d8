"""The benchmark: one cell per run, driven by the files under this directory."""
