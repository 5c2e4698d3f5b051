"""Share of the held experts that got at least one pair in a decode
step: the program's ``moe_experts_touched_total`` over
``moe_expert_slots_total`` (held experts x layers x decode steps), over
the whole run's decode steps.  What a decode step must read of the
experts' weights: at 256 slots x 10 picks over 512 experts, nearly all.
None where the program has no such counter."""

from benchmarks.harness.program_tape import registry_value


def read(_run):
    slots = registry_value("counters", "moe_expert_slots_total")
    if not slots:
        return None
    touched = registry_value("counters", "moe_experts_touched_total") or 0
    return 100.0 * touched / slots
