"""Mean (row, held expert) pairs an active held expert computed, per MoE
layer and step: the window's ``expert_rows.<phase>`` total over its
``experts_active.<phase>`` total, as the family's work accumulator keeps
them. None where the program keeps no such counters."""


def read(ctx):
    work = ctx["work"]
    active = getattr(work, "experts_active", 0)
    if not active:
        return None
    return getattr(work, "expert_rows", 0) / active
