"""Harness internals: manifest lookup, input generation, load drivers."""
