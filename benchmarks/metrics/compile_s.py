"""Seconds jax spent in backend compilation before the window opened
(jax's own compile-duration events)."""


def read(run):
    return run.compile_before_s
