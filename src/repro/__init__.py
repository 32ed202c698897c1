"""Reproduction of "Compression with Exact Error Distribution for
Federated Learning" as a sharded jax training/serving system."""
