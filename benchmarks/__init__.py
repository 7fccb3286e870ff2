"""The benchmark: data files plus a small harness. See README.md here."""
