"""Seconds of set-up spent importing: the run's ``import_and_devices``
stage (interpreter start, the harness, jax, the devices) plus its
``program_import`` stage (the program's own modules, as the kind's
driver imports them).  What is left of ``setup_s`` is weights, warm
shapes and the reference."""

STAGES = ("import_and_devices", "program_import")


def read(run):
    found = [s for name, s in run.stages if name in STAGES]
    return sum(found) if found else None
