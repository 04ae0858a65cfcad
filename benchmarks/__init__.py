"""Microbenchmarks for the transport and kernel layers."""
