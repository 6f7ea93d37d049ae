"""Readers of per-layer metrics added after the first benchmark: one
module a source, named by ``benchmark/metrics/<metric>.json``."""
