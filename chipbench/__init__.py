"""The repository's chip benchmark (see chipbench/README.md)."""
