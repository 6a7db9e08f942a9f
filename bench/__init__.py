"""The benchmark of this repository: see bench/README.md."""
