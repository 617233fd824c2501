"""Seconds JAX spent tracing, lowering and compiling during set-up
(``jax.monitoring`` compile events; programs loaded from the persistent
cache are not compiled)."""


def read(facts):
    return facts.get("compile_s")
