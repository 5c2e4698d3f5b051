"""Backend compilations between the window's opening and its close
(jax's compile-duration events).  Expected 0: every shape is warmed in
set-up."""


def read(run):
    return run.compiles_in_window
